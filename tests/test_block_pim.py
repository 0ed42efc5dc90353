"""Full-block PIM serving: block linear inventory, co-scheduled group
planning (chains by column budget, weight-stationary reuse), the model
hooks that run attention/FFN/MoE projections as PIM linears, and the
quantized ragged path's parity with the dense correction."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config
from repro.engine import Engine
from repro.pim import (PlannedWeight, QTensor, block_linears, plan_block,
                       plan_weight, qmatmul_exact, qragged_matmul_exact,
                       quantize)
from repro.pim.quant import RAGGED_TILE, STACK_AXES

pytestmark = pytest.mark.pim


def _plan_stack(we, n_bits):
    """An (E, D, F) stack planned with one scale, as the ragged path
    takes it."""
    return plan_weight(we, n_bits, STACK_AXES)


def _pim_cfg(arch="gemma2-9b", block_mode="full"):
    cfg = get_config(arch, smoke=True)
    return dataclasses.replace(cfg, pim_linear_mode="pim",
                               pim_linear_bits=8,
                               pim_block_mode=block_mode)


# ------------------------------------------------------------ inventory ----
def test_pim_scopes_follow_mode_flags():
    cfg = get_config("gemma2-9b", smoke=True)
    assert cfg.pim_scopes() == ()
    assert _pim_cfg(block_mode="none").pim_scopes() == ("head",)
    assert _pim_cfg(block_mode="ffn").pim_scopes() == ("head", "ffn")
    assert _pim_cfg(block_mode="full").pim_scopes() == ("head", "ffn",
                                                        "attn")


def test_block_linears_cover_attention_and_ffn():
    cfg = _pim_cfg()
    names = {l.name: l for l in block_linears(cfg)}
    for want in ("attn.q", "attn.k", "attn.v", "attn.o",
                 "ffn.w1", "ffn.w3", "ffn.w2", "lm_head"):
        assert want in names, want
    assert names["attn.q"].scope == "attn"
    assert names["ffn.w2"].scope == "ffn"
    assert names["lm_head"].scope == "head"
    # shapes match the model's own projection inventory
    from repro.models.attention import projection_shapes
    for pname, i, o in projection_shapes(cfg):
        assert (names[pname].in_dim, names[pname].out_dim) == (i, o)


def test_block_linears_moe_counts_active_experts():
    cfg = _pim_cfg("deepseek-moe-16b")
    names = {l.name: l for l in block_linears(cfg)}
    e = cfg.moe
    kinds = cfg.layer_kinds()
    n_moe = sum(1 for k in kinds if k == "m")
    assert names["moe.expert.w1"].count == n_moe * (e.top_k + e.n_shared)
    assert names["moe.expert.w2"].in_dim == cfg.d_ff
    assert "moe.dense.w1" in names          # the 'd' layer rides along
    assert all(l.name != "moe.router" for l in block_linears(cfg))


@pytest.mark.parametrize("held", [None, 8, 4])
def test_block_linears_mla_projections_and_held_experts(held):
    """Latent attention's projections (its wkv_b is absorbed, digital)
    and, for a MoE layer told its share, at most min(top_k, held) of a
    token's picks a layer beside the shared experts."""
    cfg = get_config("deepseek-v2-lite")
    cfg = dataclasses.replace(
        cfg, pim_linear_mode="pim", pim_block_mode="full",
        moe=dataclasses.replace(cfg.moe, experts_held=held))
    names = {l.name: l for l in block_linears(cfg)}
    assert {n for n in names if n.startswith("attn.")} == {
        "attn.q", "attn.kv_a", "attn.o"}
    assert (names["attn.q"].in_dim, names["attn.q"].out_dim) == (2048,
                                                                 16 * 192)
    assert names["attn.kv_a"].out_dim == 512 + 64
    assert (names["attn.o"].in_dim, names["attn.o"].count) == (16 * 128, 27)
    picks = 6 if held is None else min(6, held)
    assert names["moe.expert.w1"].count == 26 * (picks + 2)
    assert names["moe.dense.w1"].out_dim == 10944


def test_block_linears_encdec_counts_cross_attention_and_encoder():
    """Regression: enc-dec decoder blocks also route their
    cross-attention xq/xk/xv/xo through pim_proj, and the encoder's
    self-attention blocks share the hooks — the planner inventory must
    count both or per-scope cycles/MAC under-reports."""
    cfg = _pim_cfg("whisper-small")
    names = {l.name: l for l in block_linears(cfg)}
    kinds = cfg.layer_kinds()
    n_attn = sum(1 for k in kinds if k in ("g", "l", "m", "d"))
    for x in ("attn.xq", "attn.xk", "attn.xv", "attn.xo"):
        assert x in names, x
        assert names[x].count == n_attn          # decoder blocks only
    assert names["attn.q"].count == n_attn + cfg.enc_layers
    assert names["ffn.w1"].count >= cfg.enc_layers
    # non-encdec configs carry no cross-attention entries
    assert all(not l.name.startswith("attn.x")
               for l in block_linears(_pim_cfg("gemma2-9b")))


# ------------------------------------------------------------- planning ----
def test_plan_block_groups_by_scope_with_budgeted_chains():
    cfg = _pim_cfg()
    eng = Engine()
    plan = plan_block(cfg, eng)
    assert plan.scopes == ["head", "ffn", "attn"]
    met = plan.scope_metrics()
    ffn = met["ffn"]
    assert ffn["linears"] == ["ffn.w1", "ffn.w3", "ffn.w2"]
    assert all(c >= 1 for c in ffn["chains"])
    # chains are work-weighted: w2 streams 2x the elements of w1
    chains = dict(zip(ffn["linears"], ffn["chains"]))
    assert chains["ffn.w2"] >= chains["ffn.w1"]
    # every scope's fused pass is a real co-scheduled group
    for scope, row in met.items():
        assert row["macs_per_pass"] == sum(row["chains"])
        assert row["cycles_per_mac"] == pytest.approx(
            row["pass_cycles"] / row["macs_per_pass"])
        assert row["cycles_per_token"] > 0
        assert 0 < row["row_utilization"] <= 1
    assert plan.cycles_per_token == sum(
        max(g.cycles_per_token for g in plan.scope_groups(s))
        for s in plan.scopes)
    assert "cyc/MAC" in plan.summary()


def test_plan_block_compiles_once_and_reuses_weight_stationary_layouts():
    """Decode-step reuse: planning twice on one engine reuses the same
    fused packed tables (the weight-stationary layout) and triggers no
    recompiles after the first plan."""
    from repro.compiler import ProgramCache
    cache = ProgramCache(use_disk=False)
    eng = Engine(cache=cache)
    cfg = _pim_cfg()
    p1 = plan_block(cfg, eng)
    compiles = cache.stats()["compiles"]
    p2 = plan_block(cfg, eng)
    assert cache.stats()["compiles"] == compiles      # zero recompiles
    g1 = eng.compile_group(
        [("mac", 8)] )  # sanity: engine still serves other groups
    assert g1 is not None
    assert [g.chains for g in p1.groups] == [g.chains for g in p2.groups]


def test_plan_block_splits_oversized_scopes():
    """A scope with more linears than the crossbar holds MAC copies
    splits into several parallel crossbar groups instead of raising."""
    from repro.core.costmodel import CrossbarSpec
    eng = Engine()
    one = eng.compile("mac", 8).program.layout.n_cols
    tiny = Engine(crossbar=CrossbarSpec(cols=2 * one))   # 2 MACs max
    cfg = _pim_cfg()
    plan = plan_block(cfg, tiny, scopes=("attn",))
    gs = plan.scope_groups("attn")
    assert len(gs) == 2                                  # 4 linears / 2
    met = plan.scope_metrics()["attn"]
    assert met["crossbars"] == 2
    assert met["macs_per_pass"] == sum(met["chains"])


# ---------------------------------------------------------- model hooks ----
def test_full_block_forward_close_to_float():
    """pim_block_mode=full quantizes every projection; the output must
    stay close to the float model (8-bit per-layer error compounds but
    stays small at smoke scale)."""
    cfg = _pim_cfg()
    from repro.models import build_model
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(0).integers(
        3, cfg.vocab_size, (2, 8)))
    lp, _ = m.forward(params, toks)
    mf = build_model(dataclasses.replace(cfg, pim_linear_mode="off",
                                         pim_block_mode="none"))
    lf, _ = mf.forward(params, toks)
    rel = float(jnp.linalg.norm(lp - lf) / jnp.linalg.norm(lf))
    assert np.isfinite(rel) and rel < 0.08, rel


def test_ffn_scope_leaves_attention_dense():
    """pim_block_mode=ffn quantizes only the FFN projections: logits
    differ from both the float model and the full-block model."""
    cfg_ffn = _pim_cfg(block_mode="ffn")
    cfg_full = _pim_cfg(block_mode="full")
    from repro.models import build_model
    params = build_model(cfg_ffn).init(jax.random.PRNGKey(1))
    toks = jnp.asarray(np.random.default_rng(1).integers(
        3, cfg_ffn.vocab_size, (1, 6)))
    l_ffn, _ = build_model(cfg_ffn).forward(params, toks)
    l_full, _ = build_model(cfg_full).forward(params, toks)
    assert float(jnp.max(jnp.abs(l_ffn - l_full))) > 0


def test_moe_block_runs_under_ffn_scope():
    cfg = _pim_cfg("deepseek-moe-16b", block_mode="ffn")
    from repro.models import build_model
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(2).integers(
        3, cfg.vocab_size, (2, 4)))
    logits, _ = m.forward(params, toks)
    assert bool(jnp.isfinite(logits).all())


# ------------------------------------------------------- quantized MoE ----
def test_qragged_matmul_matches_dense_per_segment():
    """The ragged product of centred codes == the dense correction
    applied expert by expert (so the MoE path is bit-identical to
    running each expert's GEMM through qmatmul_exact)."""
    rng = np.random.default_rng(3)
    e, d, f = 3, 8, 5
    counts = jnp.asarray([4, 0, 2], jnp.int32)
    xs = jnp.asarray(rng.standard_normal((6, d)), jnp.float32)
    we = jnp.asarray(rng.standard_normal((e, d, f)), jnp.float32)
    xq = quantize(xs, 8)
    wq = quantize(we, 8)
    got = qragged_matmul_exact(xq, _plan_stack(we, 8), counts)
    lo = 0
    for ei, c in enumerate([4, 0, 2]):
        if c == 0:
            continue
        seg = xq._replace(q=xq.q[lo:lo + c])
        wseg = wq._replace(q=wq.q[ei])
        want = qmatmul_exact(seg, wseg)
        np.testing.assert_allclose(np.asarray(got[lo:lo + c]),
                                   np.asarray(want), rtol=0, atol=1e-4)
        lo += c


def test_quantized_matmuls_exact_at_model_widths():
    """Regression: the quantized GEMMs must accumulate in integers —
    float32 accumulation silently drops low bits once the per-row dot
    passes 2^24 (true for every real d_model here), breaking the
    bit-identical-to-the-crossbar claim."""
    rng = np.random.default_rng(11)
    d = 4096
    x = jnp.asarray(rng.standard_normal((4, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((d, 3)), jnp.float32)
    xq = quantize(x, 8)
    wq = quantize(w, 8, axis=0)
    got = np.asarray(qmatmul_exact(xq, wq), np.float64)
    xi = np.asarray(xq.q, np.int64) - xq.zero
    wi = np.asarray(wq.q, np.int64) - wq.zero
    want = ((xi @ wi).astype(np.float64)
            * np.asarray(xq.scale, np.float64)
            * np.asarray(wq.scale, np.float64))
    np.testing.assert_allclose(got, want, rtol=1e-6)

    we = jnp.asarray(rng.standard_normal((2, d, 3)), jnp.float32)
    counts = jnp.asarray([3, 1], jnp.int32)
    wqe = quantize(we, 8)
    got_r = np.asarray(qragged_matmul_exact(xq, _plan_stack(we, 8), counts),
                       np.float64)
    wie = np.asarray(wqe.q, np.int64) - wqe.zero
    want_r = np.concatenate([xi[:3] @ wie[0], xi[3:] @ wie[1]]).astype(
        np.float64) * np.asarray(xq.scale, np.float64) * float(wqe.scale)
    np.testing.assert_allclose(got_r, want_r, rtol=1e-6)


@pytest.mark.parametrize("n_bits,dtype", [(8, "i8"), (12, "i32")])
@pytest.mark.parametrize("tile,shapes", [
    (16, ("3x12x64", "3x64x16", "3x12x16")),   # 12 rows: every expert all
    (4, ("6x4x64", "6x64x16", "6x4x16"))])     # 3 tiles + 1 per expert
def test_ragged_product_operands_in_lowered_hlo(n_bits, dtype, tile,
                                                shapes):
    """The ragged PIM product multiplies centred codes, int8 up to 8 bits
    (exact on the MXU), in one batched product, and accumulates in
    int32: up to a tile of rows, every expert against all the rows;
    past it, tiles of each expert's own rows against their experts."""
    xs = jax.ShapeDtypeStruct((12, 64), jnp.float32)
    we = jax.ShapeDtypeStruct((3, 64, 16), jnp.float32)
    counts = jax.ShapeDtypeStruct((3,), jnp.int32)
    text = jax.jit(lambda x, w, c: qragged_matmul_exact(
        quantize(x, n_bits), _plan_stack(w, n_bits), c, tile=tile)).lower(
            xs, we, counts).as_text()
    dots = [ln for ln in text.splitlines() if "dot_general" in ln]
    assert len(dots) == 1
    x, w, out = shapes
    assert (f"(tensor<{x}x{dtype}>, tensor<{w}x{dtype}>) -> "
            f"tensor<{out}xi32>") in dots[0]


@pytest.mark.parametrize("tile", [1, 4, 7, 64])
def test_qragged_tiles_span_experts_exactly(tile):
    """Tiles of one row, of several that end inside a segment, and one
    tile of all 33 rows; empty experts and rows past the segments:
    every row gets its own expert's int64 sum, the rows past the
    segments 0."""
    rng = np.random.default_rng(5)
    counts = np.array([7, 0, 13, 1, 0, 9, 0])
    t, d, f = 33, 32, 6                       # 3 rows past the segments
    xq = quantize(jnp.asarray(rng.standard_normal((t, d)), jnp.float32), 8)
    we = jnp.asarray(rng.standard_normal((7, d, f)), jnp.float32)
    wq = quantize(we, 8)
    got = np.asarray(qragged_matmul_exact(
        xq, _plan_stack(we, 8), jnp.asarray(counts, jnp.int32), tile=tile),
        np.float64)
    xi = np.asarray(xq.q, np.int64) - xq.zero
    wi = np.asarray(wq.q, np.int64) - wq.zero
    want = np.zeros((t, f))
    lo = 0
    for j, c in enumerate(counts):
        want[lo:lo + c] = xi[lo:lo + c] @ wi[j]
        lo += c
    want *= float(xq.scale) * float(wq.scale)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert not got[lo:].any()


def test_qragged_temporaries_grow_with_rows_not_rows_times_experts():
    """At 64 experts the product's temporaries stay under an eighth of
    one (E, T, F) int32 buffer, which a batched product of every row
    against every expert holds whole (1.13 of it, compiled here)."""
    e, t, d, f = 64, 16384, 256, 512

    def product(x, w, c):
        return qragged_matmul_exact(
            QTensor(x, jnp.float32(0.01), 8, 128),
            PlannedWeight(w, jnp.full((1, 1, 1), 0.01, jnp.float32), 8), c)

    compiled = jax.jit(product).lower(
        jax.ShapeDtypeStruct((t, d), jnp.int32),
        jax.ShapeDtypeStruct((e, d, f), jnp.int8),
        jax.ShapeDtypeStruct((e,), jnp.int32)).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < e * t * f * 4 / 8


def test_engine_ragged_linear_modes():
    """The PIM grouped GEMM (``repro.pim.ragged_linear``) against the
    float one (``ragged_dot``)."""
    from repro import pim
    rng = np.random.default_rng(4)
    xs = jnp.asarray(rng.standard_normal((5, 6)), jnp.float32)
    we = jnp.asarray(rng.standard_normal((2, 6, 4)), jnp.float32)
    counts = jnp.asarray([3, 2], jnp.int32)
    yf = jax.lax.ragged_dot(xs, we, counts)
    yp = pim.ragged_linear(xs, we, counts, 8)
    assert yf.shape == yp.shape == (5, 4)
    rel = float(jnp.linalg.norm(yp - yf) / jnp.linalg.norm(yf))
    assert rel < 0.05


@pytest.mark.parametrize("rows", [12, RAGGED_TILE + 88],
                         ids=["batched", "tiled"])
@pytest.mark.parametrize("n_bits", [4, 8, 12])
def test_planned_stack_matches_the_float_stack(n_bits, rows):
    """An expert stack planned once, as ``plan_weights`` plans it (one
    program, one scale a stack), has ``quantize``'s codes less its offset
    and its scale; ``ragged_linear`` on it gives what it gives on the
    float stack, and what the centred integer sum of ``quantize``'s codes
    gives, bit for bit, up to a tile of rows and past it. Tracing counts
    a plan read on the planned stack and a quantization on the float
    one; a stack planned at another width raises."""
    from repro import pim
    rng = np.random.default_rng(n_bits + rows)
    e, d, f = 3, 32, 16
    xs = jnp.asarray(rng.standard_normal((rows, d)), jnp.float32)
    we = jnp.asarray(rng.standard_normal((e, d, f)) * 0.05, jnp.float32)
    n = [rows // 2, 0, rows - rows // 2 - 3]       # 3 rows past them
    counts = jnp.asarray(n, jnp.int32)
    planned = jax.jit(plan_weight, static_argnums=(1, 2))(
        we, n_bits, STACK_AXES)
    assert planned.scale.shape == (1, 1, 1)
    assert planned.q.dtype == (jnp.int8 if n_bits <= 8 else jnp.int32)
    ref = jax.jit(lambda w: quantize(w, n_bits))(we)
    np.testing.assert_array_equal(np.asarray(planned.q, np.int32),
                                  np.asarray(ref.q) - ref.zero)
    np.testing.assert_array_equal(planned.scale.reshape(()), ref.scale)

    reads = obs.counter(obs.STACK_PLAN_READS)
    quantizations = obs.counter(obs.STACK_QUANTIZATIONS)
    r0, q0 = reads.value, quantizations.value
    run = jax.jit(lambda x, w, c: pim.ragged_linear(x, w, c, n_bits))
    got_plan = np.asarray(run(xs, planned, counts))
    assert (reads.value - r0, quantizations.value - q0) == (1, 0)
    got_float = np.asarray(run(xs, we, counts))
    assert (reads.value - r0, quantizations.value - q0) == (1, 1)
    np.testing.assert_array_equal(got_plan, got_float)

    xq = jax.jit(lambda x: quantize(x, n_bits))(xs)
    xi = np.asarray(xq.q, np.int64) - xq.zero
    wi = np.asarray(ref.q, np.int64) - ref.zero
    want = np.zeros((rows, f), np.int64)
    lo = 0
    for j, c in enumerate(n):
        want[lo:lo + c] = xi[lo:lo + c] @ wi[j]
        lo += c
    want = (want.astype(np.float32) * np.asarray(xq.scale, np.float32)
            * np.asarray(ref.scale, np.float32))
    np.testing.assert_array_equal(got_plan, want)
    assert not got_plan[lo:].any()

    other = 8 if n_bits != 8 else 4
    with pytest.raises(ValueError, match=f"planned at {n_bits}"):
        pim.ragged_linear(xs, planned, counts, other)
