"""ISSUE-18 tentpole: the model-axis LM layouts compile through the ONE
mesh path with the compressed dp exchange.

Contracts pinned here:

  * GRAMMAR — ``MeshSpec.from_layout`` reproduces exactly the axes
    tuples ``cli.cmd_lm`` used to hand ``make_mesh``; ``layout_name`` is
    its inverse up to degenerate axes; shapes outside the grammar raise.
  * DEGENERACY — ``exchange=None`` keeps each family's legacy dp tail;
    ``DpExchange("gather")`` (the scoped compressed-stack route) is
    BIT-IDENTICAL in outputs to the legacy tail, per axis family, and
    ``build_model_axis_program`` returns exactly the direct builders'
    programs.
  * SCOPES — the ``named_phase`` anchors (``encode`` / ``exchange`` /
    ``decode_mean`` / ``ring_exchange_decode``) survive into the
    compiled HLO of every model-axis program family, so ``report
    timeline`` stays sighted on them.
  * PRICING — the pipeline bubble / tp psum / MoE all-to-all wire
    formulas, the ``lm[...]`` candidate grammar, the priced-never-probed
    ladder rows, and the honest ``MODEL_AXIS_REJECTS`` reasons.
  * RESHARD — ``reshard_model_axes`` redistributes a live lm state onto
    a tp layout bit-identically to a fresh build from the same host
    values, momentum carried exactly, round-trip exact.
  * RESUME — a recorded decision refuses a model-axis shape mismatch.
  * DELAYED (ISSUE-19) — ``overlap="delayed"`` threads the stale-by-one
    carry through the family steps: off-mode lowers byte-identical to
    the pre-PR path, anchors survive under delayed, the fused step
    replays the two-program oracle's schedule bit-exact on the
    replicated-degenerate layout, the candidate grammar emits (and the
    pricing bubble-credits) ``+delayed`` rows, and a resharded
    DelayedState resets its carry to the fresh valid=0 value.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from atomo_tpu.codecs import QsgdCodec
from atomo_tpu.controller.space import (
    MODEL_AXIS_REJECTS,
    lm_axis_candidates,
    model_axis_conflicts,
)
from atomo_tpu.mesh import reshard_model_axes
from atomo_tpu.mesh.spec import LAYOUT_MODEL_AXES, MeshSpec
from atomo_tpu.parallel.lm import DpExchange, compressed_dp_exchange
from atomo_tpu.parallel.model_axes import build_model_axis_program
from atomo_tpu.training import make_optimizer
from atomo_tpu.utils.comm_model import (
    candidate_name,
    codec_leaf_payload_bytes,
    moe_all_to_all_wire_bytes,
    overlap_report,
    pipeline_bubble_fraction,
    pipeline_bubble_s,
    predict_step_s,
    ring_allreduce_wire_bytes,
    tp_psum_wire_bytes,
)

CFG = dict(vocab_size=16, max_len=12, width=16, depth=2, num_heads=4)
CODEC = QsgdCodec(bits=8, bucket_size=512)


def _opt():
    return make_optimizer("sgd", lr=0.1, momentum=0.9)


def _tokens(seed=0, n=4, s=10):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], size=(n, s)
    ).astype(np.int32)


def _leaves_equal(a, b) -> bool:
    la = jax.tree_util.tree_leaves(jax.device_get(a))
    lb = jax.tree_util.tree_leaves(jax.device_get(b))
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb)
    )


# ------------------------------------------------------------ the grammar


def test_from_layout_reproduces_cmd_lm_axes():
    assert MeshSpec.from_layout("dp", 4).axes == (("dp", 4), ("sp", 1))
    assert MeshSpec.from_layout("dp-sp", 4, 2).axes == (
        ("dp", 2), ("sp", 2),
    )
    assert MeshSpec.from_layout("dp-tp", 4, 2).axes == (
        ("dp", 2), ("tp", 2),
    )
    assert MeshSpec.from_layout("dp-ep", 8, 4).axes == (
        ("dp", 2), ("ep", 4),
    )
    assert MeshSpec.from_layout("dp-pp", 4, 2).axes == (
        ("dp", 2), ("pp", 2),
    )
    assert MeshSpec.from_layout("dp-tp-sp", 8, (2, 2)).axes == (
        ("dp", 2), ("tp", 2), ("sp", 2),
    )


def test_from_layout_rejects_bad_inputs():
    with pytest.raises(ValueError, match="unknown layout"):
        MeshSpec.from_layout("dp-zz", 4)
    with pytest.raises(ValueError, match="does not divide"):
        MeshSpec.from_layout("dp-tp", 4, 3)
    with pytest.raises(ValueError, match=r"\(tp, sp\) pair"):
        MeshSpec.from_layout("dp-tp-sp", 8, 4)


def test_layout_name_inverts_from_layout():
    for layout in LAYOUT_MODEL_AXES:
        ways = (2, 2) if layout == "dp-tp-sp" else 2
        spec = MeshSpec.from_layout(layout, 8, ways)
        # dp x sp1 renders as dp — that IS the layout it came from
        expect = "dp" if layout == "dp" else layout
        assert spec.layout_name() == expect
    with pytest.raises(ValueError, match="not an LM model-axis layout"):
        MeshSpec.from_world(4, 2).layout_name()  # two-tier = data layout


def test_model_axes_property_includes_degenerate():
    assert MeshSpec.from_layout("dp", 4).model_axes == (("sp", 1),)
    assert MeshSpec.from_layout("dp-tp", 4, 2).model_axes == (("tp", 2),)
    assert MeshSpec.from_world(4, 2).model_axes == ()


# ------------------------------------------------- DpExchange validation


def test_dp_exchange_validates_aggregate():
    with pytest.raises(ValueError):
        DpExchange(aggregate="hierarchical")
    assert DpExchange(aggregate="ring", ring_bucket_size=1024).aggregate


def test_dp_exchange_validates_overlap():
    with pytest.raises(ValueError, match="off | delayed"):
        DpExchange(overlap="eager")
    # delayed carries an ENCODED payload; dense psum has none to carry
    with pytest.raises(ValueError, match="gather.*ring"):
        DpExchange(aggregate="psum", overlap="delayed")
    assert DpExchange(aggregate="gather", overlap="delayed").overlap
    assert DpExchange(aggregate="ring", overlap="delayed").overlap


def test_ring_exchange_requires_codec():
    with pytest.raises(ValueError, match="needs a codec"):
        compressed_dp_exchange(
            None, None, None, None, None, None,
            dp_axis="dp", n_dp=2, exchange=DpExchange(aggregate="ring"),
        )


# ------------------------------------------------------- conflict rejects


def test_model_axis_rejects_name_their_reasons():
    # overlap_delayed is GONE — the ISSUE-19 lift, delete-not-bypass
    assert set(MODEL_AXIS_REJECTS) == {
        "hierarchical", "sparse_rows", "quorum",
    }
    for reason in MODEL_AXIS_REJECTS.values():
        assert len(reason) > 20  # a statement, not a flag
    # the quorum reason names the ACTUAL remaining gap, not the old
    # "no delayed rig" story (the rig exists now)
    assert "build_model_axis_program" in MODEL_AXIS_REJECTS["quorum"]


@pytest.mark.parametrize(
    "cand,key",
    [
        ({"aggregate": "hierarchical"}, "hierarchical"),
        ({"sparse_rows": "on"}, "sparse_rows"),
        ({"quorum": 3}, "quorum"),
    ],
)
def test_model_axis_conflicts_reject_unproven(cand, key):
    assert model_axis_conflicts(cand) == MODEL_AXIS_REJECTS[key]


def test_model_axis_conflicts_delayed_lifted():
    """Delayed overlap is PROVEN on gather/ring with a codec; the only
    remaining reject is structural — a dense exchange (psum / no codec)
    has no encoded payload to carry between steps."""
    assert model_axis_conflicts(
        {"aggregate": "gather", "overlap": "delayed", "codec": "qsgd8"}
    ) is None
    assert model_axis_conflicts(
        {"aggregate": "ring", "overlap": "delayed", "codec": "qsgd8"}
    ) is None
    for bad in (
        {"aggregate": "psum", "overlap": "delayed", "codec": "qsgd8"},
        {"aggregate": "gather", "overlap": "delayed"},
    ):
        reason = model_axis_conflicts(bad)
        assert reason is not None and "payload" in reason


def test_model_axis_conflicts_pass_proven():
    for cand in (
        {"aggregate": "gather"},
        {"aggregate": "psum"},
        {"aggregate": "ring", "stream_encode": "on"},
        {"aggregate": "gather", "budget_alloc": "variance"},
    ):
        assert model_axis_conflicts(cand) is None


def test_lm_axis_candidates_grammar():
    rows = lm_axis_candidates(
        model_axes={"tp": 2}, codec_tag="qsgd8", have_budget=True,
    )
    names = [r["name"] for r in rows]
    assert "lm[tp2]+qsgd8+gather+off+k1" in names
    assert "lm[tp2]+qsgd8+gather+off+se+k1" in names
    assert "lm[tp2]+qsgd8+psum+off+ab+k1" in names
    assert any(n.startswith("lm[tp2]+qsgd8+ring") for n in names)
    for r in rows:
        assert model_axis_conflicts(r) is None
        assert r["model_axes"] == {"tp": 2}
    with pytest.raises(ValueError, match="pure data layout"):
        lm_axis_candidates(model_axes={"dp": 4})


def test_lm_axis_candidates_emit_delayed():
    """The ISSUE-19 lift in the candidate grammar: +delayed rows (plain
    and +se) for the payload-carrying aggregations when a codec is
    armed — never for psum, never without a codec."""
    rows = lm_axis_candidates(model_axes={"pp": 2}, codec_tag="qsgd8")
    names = [r["name"] for r in rows]
    assert "lm[pp2]+qsgd8+gather+delayed+k1" in names
    assert "lm[pp2]+qsgd8+gather+delayed+se+k1" in names
    assert any(
        "ring" in n and "delayed" in n and "se" not in n for n in names
    )
    assert not any("psum" in n and "delayed" in n for n in names)
    # every emitted row still passes the conflict predicate (asserted
    # inside the enumerator too — this pins it from the outside)
    for r in rows:
        assert model_axis_conflicts(r) is None
    # no codec -> no payload to carry -> no delayed rows at all
    dense = lm_axis_candidates(model_axes={"pp": 2}, codec_tag="")
    assert not any("delayed" in r["name"] for r in dense)
    # and the knob can be turned off wholesale
    off = lm_axis_candidates(
        model_axes={"pp": 2}, codec_tag="qsgd8", allow_overlap=False,
    )
    assert not any("delayed" in r["name"] for r in off)


# ------------------------------------------------------------ the pricing


def test_pipeline_bubble_formulas():
    assert pipeline_bubble_fraction(1, 4) == 0.0
    assert pipeline_bubble_fraction(4, 1) == pytest.approx(3 / 4)
    assert pipeline_bubble_fraction(2, 2) == pytest.approx(1 / 3)
    assert pipeline_bubble_s(0.12, 4, 3) == pytest.approx(0.12 * 3 / 3)
    assert pipeline_bubble_s(0.12, 1, 8) == 0.0


def test_tp_psum_and_moe_a2a_wire():
    act = 1e6
    # 2 psums/block forward + the same 2 in the backward transpose
    assert tp_psum_wire_bytes(act, 2, 3) == pytest.approx(
        4 * 3 * ring_allreduce_wire_bytes(act, 2)
    )
    assert tp_psum_wire_bytes(act, 1, 3) == 0.0
    # dispatch + return, forward + backward, (n-1)/n wired
    assert moe_all_to_all_wire_bytes(1e6, 4, 2) == pytest.approx(
        4 * 2 * 1e6 * 3 / 4
    )
    assert moe_all_to_all_wire_bytes(1e6, 1, 2) == 0.0


def test_candidate_name_lm_prefix():
    name = candidate_name({
        "model_axes": {"tp": 2}, "codec": "qsgd8",
        "aggregate": "gather", "overlap": "off", "superstep": 1,
    })
    assert name == "lm[tp2]+qsgd8+gather+off+k1"
    # degenerate and data axes stay out of the shape tag
    name3 = candidate_name({
        "model_axes": {"dp": 2, "tp": 2, "sp": 1},
        "aggregate": "psum", "overlap": "off", "superstep": 1,
    })
    assert name3.startswith("lm[tp2]+psum")


def test_predict_step_s_prices_model_axis_floor():
    kw = dict(
        dense_bytes=4e6, payload_bytes=1e6, ways=4, fabric_bw=1e9,
        compute_s=0.1,
    )
    base = {"aggregate": "gather", "overlap": "off", "superstep": 1}
    lm = dict(
        base, model_axes={"tp": 2},
        model_comm_s=0.002, pipeline_bubble_s=0.003,
    )
    assert predict_step_s(lm, **kw) - predict_step_s(base, **kw) == (
        pytest.approx(0.005)
    )
    # the floor also lands on the single-device and dense paths
    kw1 = dict(kw, ways=1)
    assert predict_step_s(lm, **kw1) - predict_step_s(base, **kw1) == (
        pytest.approx(0.005)
    )


def test_overlap_report_prices_pipeline_bubble():
    rep = overlap_report(
        dense_bytes=4e6, payload_bytes=1e6, ways=4, fabric_bw=1e9,
        compute_s=0.1, pipeline_stages=4, pipeline_microbatches=2,
    )
    assert rep["pipeline_bubble_ms"] == pytest.approx(
        pipeline_bubble_s(0.1, 4, 2) * 1e3
    )
    assert rep["pipeline_bubble_fraction"] == pytest.approx(
        pipeline_bubble_fraction(4, 2)
    )
    flat = overlap_report(
        dense_bytes=4e6, payload_bytes=1e6, ways=4, fabric_bw=1e9,
        compute_s=0.1,
    )
    assert flat["pipeline_bubble_ms"] == 0.0
    assert rep["blocking_step_ms"] - flat["blocking_step_ms"] == (
        pytest.approx(rep["pipeline_bubble_ms"])
    )


# -------------------------------------------------------- resume refusal


def test_decision_reusable_refuses_model_axis_shape():
    from atomo_tpu.tuning.autopilot import decision_reusable

    doc = {
        "complete": True,
        "winner": {"knobs": {"aggregate": "gather"}},
        "meta": {"n_devices": 4, "mesh_axes": {"dp": 2, "tp": 2}},
    }
    ok, why = decision_reusable(
        doc, n_dev=4, mesh_axes={"dp": 2, "tp": 2}
    )
    assert ok, why
    ok, why = decision_reusable(
        doc, n_dev=4, mesh_axes={"dp": 4, "sp": 1}
    )
    assert not ok
    assert "different axis shape" in why


def test_report_cross_checks_layout():
    from atomo_tpu.obs.report import _check_model_axes_layout

    ctl = {"meta": {
        "mesh_axes": {"dp": 2, "tp": 2},
        "controller": {"layout": "dp-tp", "model_axes": {"tp": 2}},
    }}
    run = {"kind": "meta", "what": "model_axes", "layout": "dp-tp",
           "mesh_axes": {"dp": 2, "tp": 2}}
    assert _check_model_axes_layout(ctl, [run])["ok"]
    contradicted = _check_model_axes_layout(
        ctl,
        [{"kind": "meta", "what": "model_axes", "layout": "dp",
          "mesh_axes": {"dp": 4, "sp": 1}}],
    )
    assert not contradicted["ok"]
    assert "dp-tp" in contradicted["detail"]
    assert _check_model_axes_layout(None, [])["skipped"]


# ------------------------------------------- compile-path byte identity


def test_compile_step_hlo_byte_identical_to_hand_rolled():
    """The one compile path IS the hand-rolled stack: same fn object,
    same mesh/specs -> byte-identical lowered text (the PR-14 contract,
    re-pinned for the lm-shaped in_specs the model-axis builders use)."""
    from jax.sharding import PartitionSpec as P

    from atomo_tpu.parallel.compile import compile_step

    spec = MeshSpec.from_layout("dp-tp", 4, 2)
    mesh = spec.build()

    def fn(state, tokens):
        return jax.tree_util.tree_map(lambda x: x * 2.0, state), tokens

    in_specs = (P(), P("dp", None))
    out_specs = (P(), P("dp", None))
    ours = compile_step(
        fn, mesh, in_specs=in_specs, out_specs=out_specs,
        donate_argnums=(0,),
    )
    hand = jax.jit(
        jax.shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        ),
        donate_argnums=(0,),
    )
    state = {"w": jnp.ones((4, 4), jnp.float32)}
    toks = jnp.zeros((4, 8), jnp.float32)
    assert ours.lower(state, toks).as_text() == hand.lower(
        state, toks
    ).as_text()


# --------------------------------------- per-family parity + HLO scopes
#
# Budget discipline (conftest): ONE tier-1 witness per contract (the
# dp-tp family), the other families ride the slow lane.


def _family_program(layout, exchange, n_dev=4, ways=2):
    cfg = dict(CFG)
    if layout == "dp-ep":
        cfg["num_experts"] = 4
    spec = MeshSpec.from_layout(layout, n_dev, ways)
    return cfg, build_model_axis_program(
        spec, cfg, _opt(), jax.random.PRNGKey(0), CODEC,
        num_microbatches=2, exchange=exchange,
    )


def _run_one(prog, seed=7):
    toks = prog.shard_tokens(_tokens(seed))
    return prog.step(
        prog.state, jax.random.PRNGKey(seed), toks
    )


def _assert_parity_and_scopes(layout, *, ways=2, n_dev=4):
    _, legacy = _family_program(layout, None, n_dev, ways)
    _, scoped = _family_program(
        layout, DpExchange(aggregate="gather"), n_dev, ways
    )
    s0, m0 = _run_one(legacy)
    s1, m1 = _run_one(scoped)
    assert _leaves_equal(s0.params, s1.params), layout
    assert float(m0["loss"]) == float(m1["loss"]), layout
    assert float(m0["msg_bytes"]) == float(m1["msg_bytes"]), layout
    # executed wire == the comm model's per-leaf payload sum priced over
    # the model-axis-LOCAL shard shapes, to the byte, and below dense
    assert int(m1["msg_bytes"]) == sum(
        codec_leaf_payload_bytes(
            CODEC, leaf.sharding.shard_shape(leaf.shape)
        )
        for leaf in jax.tree_util.tree_leaves(s1.params)
    ), layout
    assert float(m1["msg_bytes"]) < float(m1["dense_bytes"]), layout
    # the timeline anchors survive into the scoped program's HLO
    toks = scoped.shard_tokens(_tokens(1))
    txt = scoped.step.lower(
        scoped.state, jax.random.PRNGKey(1), toks
    ).compile().as_text()
    assert "encode" in txt, layout
    assert "exchange" in txt and "decode_mean" in txt, layout


def test_tp_family_parity_and_scopes():
    _assert_parity_and_scopes("dp-tp")


@pytest.mark.slow
def test_pp_family_parity_and_scopes():
    _assert_parity_and_scopes("dp-pp")


@pytest.mark.slow
def test_moe_family_parity_and_scopes():
    _assert_parity_and_scopes("dp-ep")


@pytest.mark.slow
def test_tp_sp_family_parity_and_scopes():
    _assert_parity_and_scopes("dp-tp-sp", ways=(2, 2), n_dev=8)


@pytest.mark.slow
def test_dp_family_parity_and_scopes():
    _assert_parity_and_scopes("dp", ways=1)


@pytest.mark.slow
def test_tp_family_ring_exchange():
    """Ring aggregation on a model-axis layout: same mean (allclose —
    a different reduction ORDER, same estimator), ring scope in HLO."""
    _, gather = _family_program("dp-tp", DpExchange(aggregate="gather"))
    _, ring = _family_program("dp-tp", DpExchange(aggregate="ring"))
    s0, m0 = _run_one(gather)
    s1, m1 = _run_one(ring)
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(s0.params)),
        jax.tree_util.tree_leaves(jax.device_get(s1.params)),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6
        )
    toks = ring.shard_tokens(_tokens(1))
    txt = ring.step.lower(
        ring.state, jax.random.PRNGKey(1), toks
    ).compile().as_text()
    assert "ring_exchange_decode" in txt


@pytest.mark.slow
def test_tp_family_stream_encode_parity():
    """Stream-encode re-buckets WHEN layers encode, not what: gather
    results stay bit-identical."""
    _, plain = _family_program("dp-tp", DpExchange(aggregate="gather"))
    _, streamed = _family_program(
        "dp-tp",
        DpExchange(
            aggregate="gather", stream_encode=True,
            stream_bucket_bytes=1024,
        ),
    )
    s0, m0 = _run_one(plain)
    s1, m1 = _run_one(streamed)
    assert _leaves_equal(s0.params, s1.params)
    assert float(m0["loss"]) == float(m1["loss"])


# ------------------------------------------ delayed overlap (ISSUE-19)
#
# The fill-the-bubble family: the dp exchange consumes the PREVIOUS
# step's encoded payload while this step's backward runs. Budget
# discipline: the dp-tp gather anchor drill, the replicated-degenerate
# (pure-dp) oracle parity drill and the dp-pp gates
# (test_pp_family_delayed_gates) are the tier-1 witnesses; ring and the
# dp-pp anchors ride the slow lane.


def _delayed(aggregate="gather"):
    return DpExchange(aggregate=aggregate, overlap="delayed")


def test_delayed_off_mode_hlo_byte_identical():
    """``--overlap off`` is the pre-PR path byte-for-byte: an exchange
    with the explicit field lowers to exactly the text of one that
    predates it (no carry threading leaks into the off path). Lower-only
    — no compile — so this stays a cheap tier-1 gate."""
    _, plain = _family_program("dp-tp", DpExchange(aggregate="gather"))
    _, off = _family_program(
        "dp-tp", DpExchange(aggregate="gather", overlap="off")
    )
    toks = plain.shard_tokens(_tokens(1))
    key = jax.random.PRNGKey(1)
    assert plain.step.lower(plain.state, key, toks).as_text() == (
        off.step.lower(off.state, key, toks).as_text()
    )


def test_tp_family_delayed_anchors_and_schedule():
    """dp-tp gather under delayed: the timeline anchors survive the
    compiled HLO; step 0 produces but SKIPS the apply (valid=0 carry —
    params bit-identical, though the counter still ticks); step 1
    applies the stale payload."""
    _, prog = _family_program("dp-tp", _delayed())
    toks = prog.shard_tokens(_tokens(1))
    txt = prog.step.lower(
        prog.state, jax.random.PRNGKey(1), toks
    ).compile().as_text()
    for anchor in ("encode", "exchange", "decode_mean"):
        assert anchor in txt, anchor

    assert float(jax.device_get(prog.state.carry.valid)) == 0.0
    p0 = jax.device_get(prog.state.params)
    d1, m1 = _run_one(prog)
    assert float(jax.device_get(d1.carry.valid)) == 1.0
    assert _leaves_equal(p0, d1.params)  # step-0 apply skipped
    assert 0.0 < float(m1["msg_bytes"]) < float(m1["dense_bytes"])
    d2, _ = prog.step(
        d1, jax.random.PRNGKey(8), prog.shard_tokens(_tokens(8))
    )
    assert not _leaves_equal(p0, d2.params)


def test_dp_family_delayed_oracle_parity():
    """Replicated-degenerate bit-parity drill: on the pure-dp layout the
    fused delayed step replays EXACTLY the two-program oracle's
    host-driven stale-by-one schedule — produce this step's payload from
    the PRE-apply params, apply the previous step's (step 0 skips). Full
    train tree AND carry payload bit-equal after T steps."""
    T = 3
    spec = MeshSpec.from_layout("dp", 4, 1)
    fused = build_model_axis_program(
        spec, CFG, _opt(), jax.random.PRNGKey(0), CODEC,
        num_microbatches=2, exchange=_delayed(),
    )
    oracle = build_model_axis_program(
        spec, CFG, _opt(), jax.random.PRNGKey(0), CODEC,
        num_microbatches=2, exchange=_delayed(), oracle_parts=True,
    )
    key = jax.random.PRNGKey(42)

    train = oracle.state.train
    payload = oracle.state.carry.payload
    valid = oracle.state.carry.valid
    for i in range(T):
        k = jax.random.fold_in(key, i)
        toks = oracle.shard_tokens(_tokens(100 + i))
        new_payload, _ = oracle.step["produce"](train, k, toks)
        train, _ = oracle.step["apply"](train, payload, valid)
        payload, valid = new_payload, jnp.float32(1.0)

    d = fused.state
    for i in range(T):
        k = jax.random.fold_in(key, i)
        toks = fused.shard_tokens(_tokens(100 + i))
        d, _ = fused.step(d, k, toks)

    assert _leaves_equal(d.train, train)
    assert _leaves_equal(d.carry.payload, payload)


@functools.lru_cache(maxsize=None)
def _pp_program(kind):
    """dp2 x pp2 programs the gates below share (one build and one
    compile each): blocking, explicit overlap="off", delayed, a second
    delayed build (the restarted process) and the two-program oracle."""
    exchange = {
        "blocking": DpExchange(aggregate="gather"),
        "off": DpExchange(aggregate="gather", overlap="off"),
    }.get(kind, _delayed())
    spec = MeshSpec.from_layout("dp-pp", 4, 2)
    return build_model_axis_program(
        spec, CFG, _opt(), jax.random.PRNGKey(0), CODEC,
        num_microbatches=2, exchange=exchange,
        oracle_parts=kind == "oracle",
    )


def _pp_steps(prog, state, lo, hi, key):
    state = jax.tree_util.tree_map(jnp.copy, state)  # the step donates it
    for i in range(lo, hi):
        state, m = prog.step(
            state, jax.random.fold_in(key, i),
            prog.shard_tokens(_tokens(100 + i)),
        )
    return state, m


@pytest.mark.parametrize(
    "gate", ["off_hlo", "equal_wire", "oracle_parity", "carry_resume"]
)
def test_pp_family_delayed_gates(gate, tmp_path):
    """The pipelined family, where the bubble the carry fills exists:
    ``overlap="off"`` lowers the blocking program byte for byte; delayed
    moves the blocking step's bytes; the fused delayed step replays the
    host-driven produce/apply oracle bit for bit (params AND carry); and
    T steps + save + fresh build + load + place + T steps equals 2T
    uninterrupted steps bit for bit (params AND carry)."""
    T = 2
    key = jax.random.PRNGKey(42)
    if gate == "off_hlo":
        plain, off = _pp_program("blocking"), _pp_program("off")
        toks = plain.shard_tokens(_tokens(1))
        assert plain.step.lower(plain.state, key, toks).as_text() == (
            off.step.lower(off.state, key, toks).as_text()
        )
        return
    fused = _pp_program("delayed")
    if gate == "equal_wire":
        blocking = _pp_program("blocking")
        _, md = _pp_steps(fused, fused.state, 0, 2, key)
        _, mb = _pp_steps(blocking, blocking.state, 0, 1, key)
        assert float(md["msg_bytes"]) == float(mb["msg_bytes"])
        assert 0.0 < float(md["msg_bytes"]) < float(md["dense_bytes"])
    elif gate == "oracle_parity":
        oracle = _pp_program("oracle")
        train = oracle.state.train
        payload = oracle.state.carry.payload
        valid = oracle.state.carry.valid
        for i in range(2 * T):
            k = jax.random.fold_in(key, i)
            toks = oracle.shard_tokens(_tokens(100 + i))
            new_payload, _ = oracle.step["produce"](train, k, toks)
            train, _ = oracle.step["apply"](train, payload, valid)
            payload, valid = new_payload, jnp.float32(1.0)
        d, _ = _pp_steps(fused, fused.state, 0, 2 * T, key)
        assert _leaves_equal(d.train, train)
        assert _leaves_equal(d.carry.payload, payload)
    else:
        from jax.sharding import NamedSharding

        from atomo_tpu.parallel.lm import place_model_axis_carry
        from atomo_tpu.parallel.replicated import DelayedState
        from atomo_tpu.training.checkpoint import (
            load_checkpoint,
            save_checkpoint,
        )

        whole, _ = _pp_steps(fused, fused.state, 0, 2 * T, key)
        half, _ = _pp_steps(fused, fused.state, 0, T, key)
        save_checkpoint(str(tmp_path), half)
        fresh = _pp_program("restarted")
        host = load_checkpoint(str(tmp_path), jax.device_get(fresh.state))
        resumed = DelayedState(
            train=jax.tree_util.tree_map(
                lambda leaf, sp: jax.device_put(
                    leaf, NamedSharding(fresh.mesh, sp)
                ),
                host.train, fresh.state_specs,
            ),
            carry=place_model_axis_carry(fresh.mesh, host.carry),
        )
        resumed, _ = _pp_steps(fresh, resumed, T, 2 * T, key)
        assert _leaves_equal(whole.train.params, resumed.train.params)
        assert _leaves_equal(whole.carry.payload, resumed.carry.payload)


@pytest.mark.slow
def test_tp_family_delayed_ring_anchor():
    """Ring aggregation composes with the delayed carry on dp-tp: the
    ring scope survives the compiled HLO and the step runs (step-0 skip
    intact)."""
    _, prog = _family_program("dp-tp", _delayed("ring"))
    toks = prog.shard_tokens(_tokens(1))
    txt = prog.step.lower(
        prog.state, jax.random.PRNGKey(1), toks
    ).compile().as_text()
    assert "ring_exchange_decode" in txt and "encode" in txt
    p0 = jax.device_get(prog.state.params)
    d1, _ = _run_one(prog)
    assert float(jax.device_get(d1.carry.valid)) == 1.0
    assert _leaves_equal(p0, d1.params)


@pytest.mark.slow
def test_pp_family_delayed_anchors():
    """The pipelined family — where the bubble the carry fills actually
    exists — keeps its anchors under delayed, for gather AND ring."""
    for agg, anchor in (("gather", "decode_mean"),
                        ("ring", "ring_exchange_decode")):
        _, prog = _family_program("dp-pp", _delayed(agg))
        toks = prog.shard_tokens(_tokens(1))
        txt = prog.step.lower(
            prog.state, jax.random.PRNGKey(1), toks
        ).compile().as_text()
        assert "encode" in txt and anchor in txt, agg
        d1, _ = _run_one(prog)
        assert float(jax.device_get(d1.carry.valid)) == 1.0, agg


def test_overlap_report_credits_bubble_under_delayed():
    """The pricing half of the lift: under delayed the pipeline bubble
    is ALSO hiding budget — exposed = max(0, comm - compute - bubble) —
    and the report names the credited slice (bubble_hidden_ms)."""
    kw = dict(dense_bytes=4e6, payload_bytes=1e6, ways=4, fabric_bw=1e9)
    rep = overlap_report(
        compute_s=0.0005, pipeline_stages=4, pipeline_microbatches=2,
        **kw,
    )
    bubble = pipeline_bubble_s(0.0005, 4, 2)
    comm = rep["comm_chain_ms"] / 1e3
    exposed = max(0.0, comm - 0.0005)
    assert rep["bubble_hidden_ms"] == pytest.approx(
        min(exposed, bubble) * 1e3, abs=2e-3
    )
    assert rep["bubble_hidden_ms"] > 0.0
    # exposed_ms keeps its compute-only meaning; only delayed_step_ms
    # takes the bubble credit
    assert rep["exposed_ms"] == pytest.approx(exposed * 1e3, abs=2e-3)
    want_exposed = max(0.0, comm - 0.0005 - bubble)
    assert rep["delayed_step_ms"] == pytest.approx(
        (0.0005 + want_exposed + bubble) * 1e3
        + rep["encode_exposed_ms"],
        abs=2e-3,
    )
    flat = overlap_report(compute_s=0.0005, **kw)
    assert flat["bubble_hidden_ms"] == 0.0


def test_predict_step_s_credits_bubble_for_delayed():
    """A delayed candidate's predicted step hides its exchange behind
    compute PLUS the pipeline bubble: with a bubble big enough to
    swallow the whole chain, adding it costs LESS than its floor (the
    exchange it ate), and the floor itself is never waived."""
    kw = dict(
        dense_bytes=4e6, payload_bytes=4e6, ways=4, fabric_bw=1e9,
        compute_s=0.001,
    )
    cand = {
        "aggregate": "gather", "overlap": "delayed", "superstep": 1,
        "model_axes": {"pp": 2}, "pipeline_bubble_s": 0.1,
    }
    with_bubble = predict_step_s(cand, **kw)
    no_bubble = predict_step_s(dict(cand, pipeline_bubble_s=0.0), **kw)
    # the 4 MB gather chain (~12 ms) dwarfs the 1 ms compute, so without
    # the bubble most of it is exposed; the 100 ms bubble hides ALL of
    # it — the delta is strictly less than the 100 ms floor
    assert with_bubble - no_bubble < 0.1
    assert with_bubble >= 0.001 + 0.1  # the bubble floor is still paid
    # a blocking candidate with the same bubble pays the full chain
    blocking = predict_step_s(
        dict(cand, overlap="off"), **kw
    )
    assert blocking > with_bubble


# --------------------------------------------------------------- reshard


def test_reshard_lm_to_tp_equals_fresh_build():
    """reshard == fresh-build from the same host values (bit-exact,
    momentum included), and the tp->lm round-trip restores the original
    tree exactly. No step compile needed — this is a data-movement
    contract."""
    from atomo_tpu.parallel.tp import (
        lm_params_to_tp,
        make_tp_state_specs,
        shard_tp_state,
        tp_param_specs,
    )
    from atomo_tpu.training.trainer import TrainState

    spec_dp = MeshSpec.from_layout("dp", 4)
    prog = build_model_axis_program(
        spec_dp, CFG, _opt(), jax.random.PRNGKey(0), CODEC
    )
    # seed non-trivial momentum without compiling a step
    host = jax.device_get(prog.state)
    mom = jax.tree_util.tree_map(
        lambda p: np.asarray(p) * 0.5, host.params
    )
    opt_state = jax.tree_util.tree_map(lambda x: x, host.opt_state)
    p_def = jax.tree_util.tree_structure(host.params)

    def params_like(n):
        return jax.tree_util.tree_structure(n) == p_def

    opt_state = jax.tree_util.tree_map(
        lambda sub: mom if params_like(sub) else sub,
        opt_state, is_leaf=params_like,
    )
    state = TrainState(
        step=host.step, params=host.params, batch_stats={},
        opt_state=opt_state,
    )
    spec_tp = MeshSpec.from_layout("dp-tp", 4, 2)
    mesh, got, specs = reshard_model_axes(state, spec_dp, spec_tp, CFG)
    assert specs is not None

    # oracle: the same bijection applied by hand + a fresh shard
    params_tp = lm_params_to_tp(host.params, CFG["num_heads"])
    opt_tp = jax.tree_util.tree_map(
        lambda sub: (
            lm_params_to_tp(sub, CFG["num_heads"])
            if params_like(sub) else sub
        ),
        opt_state, is_leaf=params_like,
    )
    want_host = TrainState(
        step=jnp.asarray(host.step, jnp.int32), params=params_tp,
        batch_stats={}, opt_state=opt_tp,
    )
    want = shard_tp_state(
        mesh, want_host,
        make_tp_state_specs(want_host, tp_param_specs(params_tp, "tp")),
    )
    assert _leaves_equal(got, want)

    # round-trip tp -> lm restores the original tree bit-for-bit
    _, back, back_specs = reshard_model_axes(got, spec_tp, spec_dp, CFG)
    assert back_specs is None
    assert _leaves_equal(back.params, host.params)


def test_reshard_rejects_layout_owned_trees():
    spec_dp = MeshSpec.from_layout("dp", 4)
    prog = build_model_axis_program(
        spec_dp, CFG, _opt(), jax.random.PRNGKey(0), None
    )
    with pytest.raises(ValueError, match="layout-owned param tree"):
        reshard_model_axes(
            prog.state, spec_dp, MeshSpec.from_layout("dp-ep", 4, 2), CFG
        )


def test_reshard_delayed_state_resets_carry():
    """Resharding a DelayedState: the TRAIN half rides the param
    bijection exactly as a bare TrainState would, and the carry RESETS
    to the fresh valid=0 value on the new layout (the old payload shards
    are the OLD layout's local slices — no bijection exists). Needs the
    run's codec to shape the fresh zero payload; refuses without it."""
    from atomo_tpu.parallel.replicated import DelayedState

    spec_dp = MeshSpec.from_layout("dp", 4)
    spec_tp = MeshSpec.from_layout("dp-tp", 4, 2)
    prog = build_model_axis_program(
        spec_dp, CFG, _opt(), jax.random.PRNGKey(0), CODEC,
        exchange=_delayed(),
    )
    assert isinstance(prog.state, DelayedState)
    with pytest.raises(ValueError, match="needs the run's codec"):
        reshard_model_axes(prog.state, spec_dp, spec_tp, CFG)

    mesh, got, specs = reshard_model_axes(
        prog.state, spec_dp, spec_tp, CFG, codec=CODEC
    )
    assert isinstance(got, DelayedState)
    assert float(jax.device_get(got.carry.valid)) == 0.0
    # the train half matches a bare-TrainState reshard bit-for-bit
    _, want, _ = reshard_model_axes(
        jax.device_get(prog.state.train), spec_dp, spec_tp, CFG
    )
    assert _leaves_equal(got.train, want)
    # the fresh carry's payload shapes come from the NEW layout's local
    # shards: identical to a fresh dp-tp delayed build's carry
    fresh = build_model_axis_program(
        spec_tp, CFG, _opt(), jax.random.PRNGKey(0), CODEC,
        exchange=_delayed(),
    )
    assert _leaves_equal(got.carry, fresh.state.carry)
