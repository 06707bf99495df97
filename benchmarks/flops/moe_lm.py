"""FLOPs that one optimizer step of a latent-attention decoder with routed
experts and a multi-token-prediction module needs, from shapes. A multiply-add
is 2. Backward is twice the forward. Embedding lookups, norms, the rotation,
softmax, the routing's sort and gathers and anything recomputed are left out.

Per layer: the latent attention's five projections and its causal products
over the S(S+1)/2 pairs (queries and keys of nope + rope, values of their own
size); then the dense gated FFN (the first `first_k_dense_replace` layers) or
the router, the shared expert and the routed experts. **The routed experts are
counted at the rows uniform routing gives this chip's share**: tokens x
experts per token x experts held / the router's outputs, a layer; the step's
own count is the `moe_held_row_bytes` counter, which `expert_work` takes.
The head is counted twice, over the S-1 positions of the main loss and the S-2
of the prediction module's, whose block runs over S-1 positions.
"""


def _latent_attention(cfg: dict, batch: int, seq: int) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    weights = d * rq + rq * h * (nope + rope) + d * (rkv + rope) + rkv * h * (nope + dv) + h * dv * d
    pairs = seq * (seq + 1) // 2
    return 2 * batch * seq * weights + 2 * batch * h * pairs * (nope + rope + dv)


def expected_rows(cfg: dict, tokens: int) -> float:
    """Rows the held experts of one layer get under uniform routing."""
    return tokens * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / cfg["routed_experts_total"]


def _expert_layer(cfg: dict, tokens: int) -> float:
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    router = 2 * tokens * d * cfg["routed_experts_total"]
    shared = 2 * tokens * 3 * d * fe * cfg["n_shared_experts"]
    return router + shared + 2 * expected_rows(cfg, tokens) * 3 * d * fe


def forward_flops(cfg: dict, batch: int, seq: int) -> float:
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    dense = cfg["first_k_dense_replace"]
    layers, mtp = cfg["num_hidden_layers"], cfg["num_nextn_predict_layers"]
    tokens = batch * seq
    total = layers * _latent_attention(cfg, batch, seq)
    total += dense * 2 * tokens * 3 * d * cfg["intermediate_size"]
    total += (layers - dense) * _expert_layer(cfg, tokens)
    total += 2 * batch * (seq - 1) * d * vocab  # the last position predicts nothing
    if mtp:
        total += 2 * batch * (seq - 1) * 2 * d * d  # [embedding | hidden] W_eh
        total += _latent_attention(cfg, batch, seq - 1) + _expert_layer(cfg, batch * (seq - 1))
        total += 2 * batch * (seq - 2) * d * vocab
    return total


def train_flops_per_step(cfg: dict, flags: dict) -> float:
    return 3 * forward_flops(cfg, int(flags["--batch-size"]), int(flags["--seq-len"]))


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] + cfg["num_nextn_predict_layers"]


def _itemsize(flags: dict) -> int:
    return 2 if flags.get("--bf16") else 4


def row_bytes(cfg: dict, flags: dict) -> int:
    """Bytes of one token row as the experts read it."""
    return cfg["hidden_size"] * _itemsize(flags)


def expert_work(cfg: dict, flags: dict, rows: float) -> tuple[float, float]:
    """(FLOPs, bytes) of the routed experts' three grouped products in one
    step, forward and backward, for the `rows` assignments the step computed
    over all its expert layers (its own count): whatever implements them.
    The bytes are what the products have to move: each held expert's three
    matrices read three times (forward, the rows' gradient, their own) and
    their gradients written once, in the compute type; a row read and its
    result written by the forward pass, and the same for their cotangents."""
    d, fe, item = cfg["hidden_size"], cfg["moe_intermediate_size"], _itemsize(flags)
    flops = 3 * 2 * rows * 3 * d * fe
    weights = expert_layers(cfg) * cfg["n_routed_experts"] * 3 * d * fe * item
    return flops, 4 * weights + 4 * rows * d * item
