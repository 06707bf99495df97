"""FLOPs that one optimizer step of a decoder of grouped-query attention
(windowed in some layers, full in the others) over routed experts needs,
from shapes. A multiply-add is 2. Backward is twice the forward. Embedding
lookups, norms, the rotation, softmax, the routing's sort and gathers and
anything recomputed are left out.

Per layer: the attention's projections ([W_q | W_k | W_v] and W_o) and its
two products over exactly the (query, key) pairs the layer's kind sees,
S(S+1)/2 in a full layer and W(W+1)/2 + (S-W)W under a window of W; then the
router and the routed experts. **The routed experts are counted at the rows
uniform routing gives this chip's share**: tokens x experts per token x
experts held / the router's outputs, a layer; the step's own count is the
`moe_held_row_bytes` counter, which `expert_work` takes. The head is counted
once, over the S-1 positions of the loss.
"""


def pairs(kind: str, seq: int, window: int) -> int:
    """(query, key) pairs a head of one sequence sees in a layer of `kind`."""
    if kind == "sliding_attention" and window < seq:
        return window * (window + 1) // 2 + (seq - window) * window
    return seq * (seq + 1) // 2


def step_pairs(cfg: dict, seq: int) -> int:
    """Pairs a head of one sequence sees over all the layers held."""
    kinds = cfg["layer_types"][: cfg["num_hidden_layers"]]
    return sum(pairs(kind, seq, cfg["sliding_window"]) for kind in kinds)


def _core_forward(cfg: dict, batch: int, seq: int) -> int:
    """q.k and p.v over the pairs: 2 x head_dim each a pair and query head."""
    return 4 * cfg["head_dim"] * batch * cfg["num_attention_heads"] * step_pairs(cfg, seq)


def expected_rows(cfg: dict, tokens: int) -> float:
    """Rows the held experts of one layer get under uniform routing."""
    return tokens * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / cfg["routed_experts_total"]


def forward_flops(cfg: dict, batch: int, seq: int) -> float:
    d, vocab, layers = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    h, hk, dh, fe = (cfg[k] for k in ("num_attention_heads", "num_key_value_heads", "head_dim",
                                      "moe_intermediate_size"))
    tokens = batch * seq
    projections = d * (h + 2 * hk) * dh + h * dh * d
    router = 2 * tokens * d * cfg["routed_experts_total"]
    experts = 2 * expected_rows(cfg, tokens) * 3 * d * fe
    total = layers * (2 * tokens * projections + router + experts) + _core_forward(cfg, batch, seq)
    return total + 2 * batch * (seq - 1) * d * vocab  # the last position predicts nothing


def train_flops_per_step(cfg: dict, flags: dict) -> float:
    return 3 * forward_flops(cfg, int(flags["--batch-size"]), int(flags["--seq-len"]))


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


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


def attention_work(cfg: dict, flags: dict) -> tuple[float, float]:
    """(FLOPs, bytes) of the attention core of one step over all the layers
    held, forward and backward: **the same work whatever implements it**. The
    FLOPs are the two forward products over the pairs each layer's kind sees
    and, backward, the five that rebuild p and give dq, dk and dv (2.5 times
    the forward). The bytes are what the core has to move, in the compute
    type: forward q, k, v in and o out; backward q, k, v, o and do in and
    dq, dk, dv out; q, o and their cotangents at the query heads, k, v and
    theirs at the key/value heads. A kernel that computes dead tiles or
    heads broadcast to the queries' count reads a lower share of this, never
    a higher one; the forward pass that `--remat dots` runs again is counted
    once."""
    batch, seq = int(flags["--batch-size"]), int(flags["--seq-len"])
    h, hk, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    flops = 3.5 * _core_forward(cfg, batch, seq)
    moved = cfg["num_hidden_layers"] * 6 * (h + hk) * batch * seq * dh * _itemsize(flags)
    return flops, moved
