"""Analytic comm-cost model invariants (atomo_tpu/utils/comm_model.py).

The measured side lives in scripts/comm_crossover.py (8-device exchange
timings, host platform); these tests pin the model algebra.
"""

import math

from atomo_tpu.utils.comm_model import (
    crossover_bandwidth,
    crossover_report,
    gather_buffer_bytes,
    max_beneficial_ways,
    ring_allgather_wire_bytes,
    ring_allreduce_wire_bytes,
    ring_stream_wire_bytes,
)

D = 44.7e6  # dense ResNet-18 gradient bytes
P = 0.62e6  # rank-3 payload bytes


def test_wire_byte_formulas():
    # all-reduce saturates at 2D as N grows; all-gather grows ~linearly
    assert ring_allreduce_wire_bytes(D, 2) == D
    assert abs(ring_allreduce_wire_bytes(D, 1 << 20) - 2 * D) < 1e-3 * D
    assert ring_allgather_wire_bytes(P, 8) == P * 7


def test_ring_stream_wire_and_buffer_accounting():
    """PR-3 Msg(MB) honesty: ring mode's wire = the N-1 ppermute payload
    hops (exactly the ring all_gather's hop traffic) PLUS the dense/N
    segment all_gather it pays for exact cross-chip determinism; the win
    it buys is the O(N·payload) gathered buffer never existing."""
    n = 8
    assert ring_stream_wire_bytes(P, D, n) == (
        ring_allgather_wire_bytes(P, n) + D * (n - 1) / n
    )
    # ring ALWAYS moves more wire than gather — the accounting must never
    # pretend otherwise (the model's stated reason ring is a memory/
    # overlap tool, not a bytes tool)
    for ways in (2, 8, 64, 256):
        assert ring_stream_wire_bytes(P, D, ways) > ring_allgather_wire_bytes(
            P, ways
        )
    # the buffer ring deletes grows linearly with N; dense-gradient-sized
    # at exactly N = byte reduction
    assert gather_buffer_bytes(P, 8) == 8 * P
    n_eq = D / P
    assert abs(gather_buffer_bytes(P, n_eq) - D) < 1e-6 * D


def test_max_beneficial_ways_is_twice_reduction():
    red = D / P
    assert abs(max_beneficial_ways(D, P) - 2 * red) < 1e-9
    # beyond that N, the gather moves MORE bytes than the all-reduce
    n_star = int(max_beneficial_ways(D, P))
    assert ring_allgather_wire_bytes(P, n_star + 5) > ring_allreduce_wire_bytes(
        D, n_star + 5
    )
    assert ring_allgather_wire_bytes(P, n_star - 5) < ring_allreduce_wire_bytes(
        D, n_star - 5
    )


def test_crossover_bandwidth_semantics():
    tax = 2.5e-3
    bw = crossover_bandwidth(D, P, 8, tax)
    # below the crossover bandwidth compression must win, above it lose
    for frac, wins in ((0.5, True), (2.0, False)):
        b = bw * frac
        t_dense = ring_allreduce_wire_bytes(D, 8) / b
        t_svd = tax + ring_allgather_wire_bytes(P, 8) / b
        assert (t_svd < t_dense) == wins
    # zero tax -> compression wins at any bandwidth
    assert crossover_bandwidth(D, P, 8, 0.0) == float("inf")
    # negative byte saving (payload too big for this N) -> never wins
    assert crossover_bandwidth(D, D, 8, tax) is None


def test_crossover_report_shape_and_consistency():
    rep = crossover_report(D, P, dense_step_s=6.5e-3, svd_step_s=9.0e-3)
    assert rep["codec_tax_ms"] == 2.5
    assert [r["ways"] for r in rep["ways"]] == [8, 16, 32, 64]
    for row in rep["ways"]:
        for label, cell in row["implied"].items():
            # speedup must equal the ratio of the implied step times
            assert math.isclose(
                cell["speedup"], cell["dense_ms"] / cell["compressed_ms"], rel_tol=5e-3
            )
        # the slowest fabric must favor compression the most
        sp = [row["implied"][k]["speedup"] for k in
              ("ici_45GBps", "dcn_6.25GBps", "eth10G_1.25GBps")]
        assert sp[0] < sp[1] < sp[2]
    # compression must lose on ICI at single-chip tax, win on 10GbE (the
    # printed story of artifacts/COMM_CROSSOVER.md)
    w8 = rep["ways"][0]["implied"]
    assert w8["ici_45GBps"]["speedup"] < 1.0 < w8["eth10G_1.25GBps"]["speedup"]


def test_overlap_hidden_exposed_algebra():
    """PR-4: overlap hides min(comm, compute) and exposes the excess —
    the two must always sum back to the full comm chain, and clamp at 0."""
    from atomo_tpu.utils.comm_model import (
        overlap_exposed_comm_s,
        overlap_hidden_comm_s,
    )

    for comm, comp in ((0.004, 0.010), (0.010, 0.004), (0.0, 0.01),
                       (0.01, 0.0)):
        hidden = overlap_hidden_comm_s(comm, comp)
        exposed = overlap_exposed_comm_s(comm, comp)
        assert hidden == min(comm, comp)
        assert abs(hidden + exposed - comm) < 1e-12
        assert hidden >= 0 and exposed >= 0


def test_overlap_report_models_both_modes():
    """The delayed step is compute + exposed, the blocking step is
    compute + chain; hidden + exposed == chain; ring mode charges ring's
    honest wire. All JSON-safe."""
    import json

    from atomo_tpu.utils.comm_model import (
        overlap_report,
        ring_allgather_wire_bytes,
        ring_stream_wire_bytes,
    )

    rep = overlap_report(
        dense_bytes=D, payload_bytes=P, ways=8, fabric_bw=1.25e9,
        compute_s=6.5e-3, decode_s=1.0e-3,
    )
    assert rep["wire_mb_per_chip"] == round(
        ring_allgather_wire_bytes(P, 8) / 1e6, 3
    )
    assert abs(
        rep["hidden_ms"] + rep["exposed_ms"] - rep["comm_chain_ms"]
    ) < 1e-6
    assert abs(
        rep["blocking_step_ms"]
        - (rep["compute_ms"] + rep["comm_chain_ms"])
    ) < 1e-6
    assert abs(
        rep["delayed_step_ms"] - (rep["compute_ms"] + rep["exposed_ms"])
    ) < 1e-6
    # a comm chain that fits under compute leaves ZERO exposed: the
    # delayed step time equals the compute-only step
    small = overlap_report(
        dense_bytes=D, payload_bytes=P, ways=8, fabric_bw=45e9,
        compute_s=6.5e-3,
    )
    assert small["exposed_ms"] == 0.0
    assert small["delayed_step_ms"] == small["compute_ms"]
    ring = overlap_report(
        dense_bytes=D, payload_bytes=P, ways=8, fabric_bw=1.25e9,
        compute_s=6.5e-3, aggregate="ring",
    )
    assert ring["wire_mb_per_chip"] == round(
        ring_stream_wire_bytes(P, D, 8) / 1e6, 3
    )
    json.dumps(rep, allow_nan=False)


def test_codec_leaf_payload_bytes_prices_clamped_actual():
    """The fixed-budget honesty regression (ISSUE-15 satellite): analytic
    per-leaf pricing must equal jax.eval_shape over the REAL encode for
    every sampler/algorithm/wire-dtype — including the layers whose full
    rank CLAMPS the configured budget (r_full < rank, and r_full <
    rank + budget_slack for the Bernoulli-budget sampler) and the
    dense-fallback layers. A nominal rank+slack slot count would
    overprice exactly those layers."""
    import jax
    import jax.numpy as jnp

    from atomo_tpu.codecs import SvdCodec, payload_nbytes
    from atomo_tpu.utils.comm_model import codec_leaf_payload_bytes

    # shapes chosen to hit every branch: tiny (dense fallback), small
    # (clamped full rank below rank+slack), mid (gram), large
    # (randomized sketch + probe atoms)
    shapes = [(10,), (4, 3), (50,), (5, 5, 10, 20), (320, 50), (800, 500)]
    codecs = [
        SvdCodec(rank=3),
        SvdCodec(rank=3, algorithm="exact"),
        SvdCodec(rank=3, algorithm="randomized"),
        SvdCodec(rank=3, sample="bernoulli_budget", budget_slack=4),
        SvdCodec(rank=3, sample="bernoulli"),
        SvdCodec(rank=3, sample="topk"),
        SvdCodec(rank=3, wire_dtype="bfloat16"),
        SvdCodec(rank=12, sample="bernoulli_budget", budget_slack=6),
    ]
    for codec in codecs:
        for shape in shapes:
            analytic = codec_leaf_payload_bytes(codec, shape)
            ev = payload_nbytes(jax.eval_shape(
                lambda c=codec, s=shape: c.encode(
                    jax.random.PRNGKey(0), jnp.zeros(s, jnp.float32)
                )
            ))
            assert analytic == ev, (codec.sample, codec.algorithm,
                                    codec.wire_dtype, shape, analytic, ev)
    # the clamp is REAL for the bernoulli budget on a small matrix:
    # (50,) resizes to (8, 7) — full rank 7, far below 12 + 6 = 18
    # nominal slots. Under the near-square matricization a payload
    # clamped to full rank always REACHES the dense fallback
    # (r_full*(m+n+1) >= m*n whenever min(m,n) <= r_full), so the
    # clamped actual IS the exact 200-byte DensePayload — a nominal
    # 18-slot pricing would charge ~6x that
    bb = SvdCodec(rank=12, sample="bernoulli_budget", budget_slack=6)
    m, n, k_nom = 8, 7, 12 + 6
    nominal = (m * k_nom + k_nom * n) * 4 + k_nom * 4
    actual = codec_leaf_payload_bytes(bb, (50,))
    assert actual == 50 * 4  # the dense fallback: the clamped actual
    assert actual < nominal
    # eval_shape fallback path for codecs without analytic pricing
    from atomo_tpu.codecs import QsgdCodec

    q = QsgdCodec(bits=4, bucket_size=128)
    ev = payload_nbytes(jax.eval_shape(
        lambda: q.encode(
            jax.random.PRNGKey(0), jnp.zeros((320, 50), jnp.float32)
        )
    ))
    assert codec_leaf_payload_bytes(q, (320, 50)) == ev


def test_budget_candidates_emitted_and_priced():
    """The +ab candidate family: emitted only for plain blocking
    gather/ring points, named with the ab suffix, priced from the
    allocation's per-leaf pairs through the one honest accounting
    function."""
    from atomo_tpu.utils.comm_model import (
        enumerate_candidates,
        leaf_budget_totals,
        predict_step_s,
        rank_candidates,
    )

    lb = [(1000.0, 100.0), (2000.0, 150.0)]
    cands = enumerate_candidates(
        has_codec=True, ways=4, allow_budget=True,
        budget_leaf_budgets=lb, allow_stream=True,
    )
    ab = [c for c in cands if c.get("budget_alloc") == "variance"]
    assert ab and all("+ab" in c["name"] for c in ab)
    # only plain blocking gather/ring variants gain +ab
    for c in ab:
        assert c["aggregate"] in ("gather", "ring")
        assert c.get("overlap", "off") == "off"
        assert c.get("stream_encode") != "on"
    # pricing: the +ab candidate's wire comes from the allocation pairs
    d, p = leaf_budget_totals(lb)
    plain = dict(ab[0])
    plain.pop("budget_alloc")
    t_ab = predict_step_s(
        ab[0], dense_bytes=d, payload_bytes=9e9, ways=4, fabric_bw=1e9,
        compute_s=1e-3, tax_s=0.0, budget_leaf_budgets=lb,
    )
    t_plain = predict_step_s(
        plain, dense_bytes=d, payload_bytes=p, ways=4, fabric_bw=1e9,
        compute_s=1e-3, tax_s=0.0,
    )
    assert t_ab == t_plain  # same bytes -> same prediction; the bogus
    # whole-tree payload_bytes=9e9 was ignored for the +ab candidate
    rows = rank_candidates(
        cands, dense_bytes=d, payload_bytes=p, ways=4, fabric_bw=1e9,
        compute_s=1e-3, tax_s=0.0, budget_leaf_budgets=lb,
    )
    assert all("predicted_ms_per_step" in r for r in rows)
    # no budgets supplied -> no +ab variants (the flag alone is not
    # enough, the sparse precedent)
    none = enumerate_candidates(has_codec=True, ways=4, allow_budget=True)
    assert not [c for c in none if c.get("budget_alloc") == "variance"]


def test_winner_knobs_carries_budget_alloc():
    from atomo_tpu.tuning.autopilot import winner_knobs

    row = {"aggregate": "gather", "overlap": "off", "superstep": 1,
           "budget_alloc": "variance", "name": "gather+off+ab+k1"}
    assert winner_knobs(row)["budget_alloc"] == "variance"
