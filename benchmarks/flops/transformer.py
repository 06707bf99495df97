"""FLOPs that one optimizer step of a decoder-only transformer needs, from
shapes. A multiply-add is 2. Forward: per token and layer the qkv, proj, up
and down matmuls (12 d^2 weights at GPT-2's 4x MLP), causal attention's two
batched matmuls over the S(S+1)/2 pairs a causal model needs, and the output
head. Backward is twice the forward. Embedding lookups, LayerNorms, softmax
and the optimizer are left out, as is anything recomputed."""


def forward_flops(cfg: dict, batch: int, seq: int) -> int:
    d, layers, vocab = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    tokens = batch * seq
    per_layer = 2 * tokens * 12 * d * d
    attention = 2 * 2 * batch * d * seq * (seq + 1) // 2
    head = 2 * batch * (seq - 1) * d * vocab  # the last position predicts nothing
    return layers * (per_layer + attention) + head


def train_flops_per_step(cfg: dict, flags: dict) -> int:
    return 3 * forward_flops(cfg, int(flags["--batch-size"]), int(flags["--seq-len"]))
