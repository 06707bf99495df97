"""The routed experts on the chip: what the CPU suite cannot see.

On the v5e `jax.lax.ragged_dot` writes only the tiles its groups cover. Rows
past the groups, which are the assignments to experts this chip does not
hold, keep whatever the memory held: non-finite values, forward and in the
rows' gradient (read on the chip, PR 33, `PERF.md` section 6). On the CPU
they are zero, so tests/test_moe_lm.py passes whether or not the layer masks
them. `models/moe.py` masks them wherever it reads them, in both passes;
plain indexing under autodiff adds them into the tokens' gradient and the
first update is NaN.
"""

import jax
import jax.numpy as jnp

import pytest

from atomo_tpu.models.moe import ExpertSizes, LatentMoeSizes, RoutedExperts

GLM = LatentMoeSizes(q_rank=8, kv_rank=8, nope_dim=8, rope_dim=8, value_dim=8,
                     expert_width=1536, experts=64, experts_held=8, route_scale=1.8)
# mellum2-1chip-dense's layer: 2 x 8192 tokens x 8 = 131,072 rows, 16 of 64 experts held
MELLUM = ExpertSizes(expert_width=896, experts=64, experts_held=16, per_token=8, scoring="softmax")


@pytest.mark.parametrize("sizes,shape", [(GLM, (2, 2048, 2048)), (MELLUM, (2, 8192, 2304))],
                         ids=["glm-16384-rows", "mellum-131072-rows"])
def test_rows_past_the_groups_never_reach_the_layers_output_or_gradients(sizes, shape):
    layer = RoutedExperts(sizes)
    k_u, k_p = jax.random.split(jax.random.PRNGKey(3))
    u = jax.random.normal(k_u, shape, jnp.bfloat16)
    params = layer.init(k_p, u)["params"]
    params = {k: v if k in ("router", "route_bias") else v.astype(jnp.bfloat16) for k, v in params.items()}
    # freed memory full of NaN: what an unwritten row then holds
    junk = [jnp.full((16384, 2048), jnp.nan, jnp.bfloat16) for _ in range(24)]
    for j in junk:
        j.block_until_ready()
    del junk

    def loss(p, u):
        return jnp.sum(layer.apply({"params": p}, u).astype(jnp.float32) ** 2)

    value, (g_params, g_u) = jax.jit(jax.value_and_grad(loss, (0, 1)))(params, u)
    assert bool(jnp.isfinite(value)) and float(value) > 0
    for name, g in {**g_params, "u": g_u}.items():
        assert bool(jnp.isfinite(g.astype(jnp.float32)).all()), name
    assert float(jnp.abs(g_u.astype(jnp.float32)).max()) > 0
    if "route_bias" in g_params:
        assert float(jnp.abs(g_params["route_bias"]).max()) == 0.0  # enters the choice alone
