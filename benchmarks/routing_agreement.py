"""Read on the chip how often the program and the plain reference route a
token differently ("Known hazard" of the glm-4.7-flash cell: top-k is
discontinuous, and a token whose fourth and fifth scores lie within the
bfloat16 path's error of each other goes to another expert): per expert
layer, the share of the T x k assignments whose expert the other side did not
choose for that token, the program computing as the cell states (bfloat16,
the router in float32) and the reference in float32 at `highest`, on the same
seeded weights and batch, forward only. One JSON line a seed. A script for the
builder, not a test; the benchmark's own runs never call this.

    python benchmarks/routing_agreement.py --workload glm47flash-1chip-dense --seeds 101,102
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def program_choices(args, params, tokens):
    """{block: chosen (T, k)} of `lm`'s model for the parsed flags."""
    import jax
    import jax.numpy as jnp

    from atomo_tpu.cli import _lm_block_config
    from atomo_tpu.models.moe import FLOAT32_LEAVES
    from atomo_tpu.models.transformer import TransformerLM
    from atomo_tpu.parallel.lm import keep_float32
    from atomo_tpu.training.trainer import cast_params
    from benchmarks.run import leaf_name

    cfg = dict(vocab_size=args.vocab_size, max_len=args.seq_len, width=args.width,
               depth=args.depth, num_heads=args.num_heads, **_lm_block_config(args))
    cfg["remat"] = "none"  # forward only
    model = TransformerLM(**cfg)
    like = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tokens))["params"]
    paths, treedef = jax.tree_util.tree_flatten_with_path(like)
    tree = jax.tree_util.tree_unflatten(treedef, [params[leaf_name(p)] for p, _ in paths])

    @jax.jit
    def forward(tree):
        low = cast_params(tree, jnp.bfloat16) if args.bf16 else tree
        low = keep_float32(low, tree, FLOAT32_LEAVES) if args.bf16 else low
        _, kept = model.apply({"params": low}, tokens, mutable=["intermediates"])
        return kept["intermediates"]

    return {block: got["moe"]["chosen"][0] for block, got in forward(tree).items()}


def reference_choices(reference, params, tokens, cfg):
    """The same from the reference's own layers, block by block."""
    import jax

    out = {}
    with jax.default_matmul_precision("highest"):
        x = params["tok_emb/embedding"][tokens]
        top = None
        for prefix, experts in reference.block_names(cfg):
            p = {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}
            if prefix == "mtp_block/":
                x = reference._mtp_input(params, params["tok_emb/embedding"][tokens[:, 1:]], top[:, :-1], cfg, "float32")
            if experts:
                mm = reference._matmul("float32")
                sub = {k[len("mla/"):]: v for k, v in p.items() if k.startswith("mla/")}
                h = x + reference._latent_attention(
                    reference._rms_norm(x, p["ln1/scale"], cfg["rms_norm_eps"]), sub, cfg, mm)
                u = reference._rms_norm(h, p["ln2/scale"], cfg["rms_norm_eps"])
                chosen, _ = reference.route(u, p["moe/router"], p["moe/route_bias"], cfg)
                out[prefix.rstrip("/")] = chosen
            x = top = jax.jit(lambda p, x, e=experts: reference._block(p, x, e, cfg, "float32")[0])(p, x)
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)

    import jax.numpy as jnp

    from atomo_tpu.cli import build_parser
    from benchmarks import run

    data = run.Data(ROOT / "BENCHMARK.json")
    cell = data.cell(args.workload)
    config, traffic = data.config(cell["config"]), data.json("traffic", cell["traffic"])
    if args.rehearse:
        config, traffic = run.tiny(config, traffic)
    reference = data.module("reference", config["reference"])
    for seed in (int(s) for s in args.seeds.split(",")):
        argv_, flags = run.program_argv(config, traffic, seed)
        parsed = build_parser().parse_args(argv_)
        params = reference.init_params(config, seed)
        tokens = jnp.asarray(reference.example_batches(config, seed, 1, int(flags["--batch-size"]))[0])
        ours = program_choices(parsed, params, tokens)
        theirs = reference_choices(reference, params, tokens, config)
        shares = {}
        for block, chosen in theirs.items():
            rows, positions, k = chosen.shape  # the module's last position has no reference
            mine = ours[block].reshape(rows, -1, k)[:, :positions]
            differ = ~(mine[..., :, None] == chosen[..., None, :]).any(-1)
            shares[block] = float(differ.mean())
        print(json.dumps({"seed": seed, "differing_assignments": shares}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
