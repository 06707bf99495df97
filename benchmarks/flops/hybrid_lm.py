"""FLOPs that one optimizer step of a hybrid decoder needs, from shapes:
layers of gated-delta-rule linear attention beside layers of full attention
(`layer_types`), each followed by a SiLU-gated FFN, and a sliced output head.
A multiply-add is 2. Backward is twice the forward. Embedding lookups, norms,
softmax, gates, the chunks' triangular inverse and anything recomputed are
left out.

The delta rule is counted in its chunked form (arXiv:2406.06484 with the decay
folded in), chunks of CHUNK tokens, whatever implements it: per chunk and head
the products K K^T and Q K^T, T (beta gamma K) and T (beta V), the three
products with the (key, value) state, and (Q K^T) U; and the causal depthwise
convolution on q, k and v.
"""

CHUNK = 64


def _sizes(cfg: dict):
    kinds = cfg["layer_types"][: cfg["num_hidden_layers"]]
    return (cfg["hidden_size"], cfg["intermediate_size"], cfg["num_attention_heads"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
            kinds.count("linear_attention"), kinds.count("full_attention"))


def delta_rule_forward_flops(cfg: dict, batch: int, seq: int) -> int:
    """One linear layer's mixer core: convolution and the chunked rule."""
    _, _, h, dk, dv, _, _ = _sizes(cfg)
    per_chunk = 2 * CHUNK * CHUNK * (3 * dk + 2 * dv) + 2 * 3 * CHUNK * dk * dv
    conv = 2 * cfg["linear_conv_kernel_dim"] * seq * h * (2 * dk + dv)
    return batch * (h * (seq // CHUNK) * per_chunk + conv)


def forward_flops(cfg: dict, batch: int, seq: int) -> int:
    d, f, h, dk, dv, n_linear, n_full = _sizes(cfg)
    tokens = batch * seq
    ffn = 2 * tokens * 3 * d * f
    full = 2 * tokens * 4 * d * d + 2 * 2 * batch * d * seq * (seq + 1) // 2
    projections = 2 * tokens * d * (2 * h * dk + 3 * h * dv + 2 * h)  # q, k; v, z, o; the two gates
    linear = projections + delta_rule_forward_flops(cfg, batch, seq)
    head = 2 * batch * (seq - 1) * d * cfg["vocab_size"]  # the last position predicts nothing
    return (n_linear + n_full) * ffn + n_full * full + n_linear * linear + head


def train_flops_per_step(cfg: dict, flags: dict) -> int:
    return 3 * forward_flops(cfg, int(flags["--batch-size"]), int(flags["--seq-len"]))


def linear_attention_work(cfg: dict, flags: dict) -> tuple[int, int]:
    """(FLOPs, bytes) of the linear layers' mixer cores in one step, forward
    and backward: from the projections' results to the output projection's
    operand (the program's `linear_attention` scope). The bytes are what the
    core has to move whatever it keeps in between: q, k, v and the output gate
    z (2 bytes, the configuration's bfloat16) and the decay's and the write
    strength's logits (float32) read and the gated, normalised output written
    by the forward pass; those read again with the output's cotangent, and
    theirs written, by the backward."""
    _, _, h, dk, dv, n_linear, _ = _sizes(cfg)
    batch, seq = int(flags["--batch-size"]), int(flags["--seq-len"])
    inputs = batch * seq * h * (2 * (2 * dk + 2 * dv) + 4 * 2)
    output = batch * seq * h * 2 * dv
    flops = 3 * delta_rule_forward_flops(cfg, batch, seq)
    return n_linear * flops, n_linear * (3 * inputs + 2 * output)
