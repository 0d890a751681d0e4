"""ops/gdn_operands.py — a DeltaNet layer's operands in one pass — in
interpreter mode on the CPU: q, k, v and z, the projection's cotangent and the
taps' gradient against models/qwen3_next.py's ``split_heads`` (``jax.vjp`` of
the XLA form, today's ``delta_heads`` arithmetic); called as the step calls it
(``vmap`` over workers, the taps shared); a length of one and of several tiles
(the halo across a tile's edge, zeros before the sequence, the last tile's
missing after-halo); one and two value heads a key head; the chooser and its
seam; the model's entry goes where the chooser says; the traced entry holds no
operand narrower than float32 and no product at all.  (The kernels compiled for
the described chip at the cell's shape: tests/test_reshard.py, where every such
program lives.)"""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aggregathor_tpu.models import qwen3_next
from aggregathor_tpu.ops import delta_rule, gdn_operands

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def grid_module(name):
    spec = importlib.util.spec_from_file_location(
        "gdn_operands_test_" + name, os.path.join(ROOT, "grid", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    sys.path.insert(0, os.path.join(ROOT, "grid"))
    try:
        spec.loader.exec_module(module)
    finally:
        sys.path.remove(os.path.join(ROOT, "grid"))
    return module


def config(key_heads, value_heads, dk, dv, taps=4, **more):
    return qwen3_next.Qwen3NextConfig(key_heads=key_heads, value_heads=value_heads, key_dim=dk,
                                      value_dim=dv, conv=taps, **more)


def seeded(lead, length, cfg, seed=3):
    """The projection's output (lead..., L, 2 keys + 2 values), the taps (2 keys
    + values, K) — shared by the workers, as a layer's leaf is — and the weights
    of a seeded scalar of the four outputs."""
    keys, values = cfg.key_heads * cfg.key_dim, cfg.value_heads * cfg.value_dim
    key = jax.random.PRNGKey(seed)
    normal = lambda place, *dims: jax.random.normal(jax.random.fold_in(key, place), dims)
    heads = lambda place, width: normal(place, *lead, length, cfg.value_heads, width)
    return (normal(0, *lead, length, 2 * keys + 2 * values),
            0.5 * normal(1, 2 * keys + values, cfg.conv),
            [heads(2, cfg.key_dim), heads(3, cfg.key_dim), heads(4, cfg.value_dim),
             heads(5, cfg.value_dim)])


def kernel_form(cfg, tile):
    """``split_heads``' contract from the kernel pair, in tiles of ``tile``."""
    def operands(projected, taps):
        by_head = lambda a, width: a.reshape(a.shape[:2] + (cfg.value_heads, width))
        q, k, v, z = gdn_operands.fused_operands(
            projected, taps, cfg.key_heads, cfg.value_heads, cfg.key_dim, cfg.value_dim,
            qwen3_next.L2_EPS, tile)
        return (by_head(q, cfg.key_dim), by_head(k, cfg.key_dim), by_head(v, cfg.value_dim),
                by_head(z, cfg.value_dim))
    return operands


def scalar_and_gradients(operands, workers, weights):
    """(the seeded scalar of q, k, v and z, the four) and its gradients to the
    projection and the taps, ``operands`` under ``vmap`` over the projection
    alone where it carries a workers' axis."""
    def scalar(projected, taps):
        outs = (jax.vmap(operands, in_axes=(0, None)) if workers else operands)(projected, taps)
        return sum(jnp.sum(out * w) for out, w in zip(outs, weights)), outs

    return jax.jit(jax.value_and_grad(scalar, argnums=(0, 1), has_aux=True))


def gap(ours, theirs):
    return float(jnp.max(jnp.abs(ours - theirs)) / jnp.max(jnp.abs(theirs)))


# (the case; workers or None; batch; length; tile; key heads; value heads; Dk; Dv; taps)
SHAPES = [("one-tile", None, 1, 16, 16, 2, 4, 16, 16, 4),
          ("several-tiles", None, 1, 64, 16, 2, 4, 16, 32, 4),
          ("tiles-of-one-group", None, 2, 32, 8, 2, 2, 16, 16, 4),
          ("workers-vmapped-one-tile", 3, 1, 16, 16, 2, 4, 16, 16, 4),
          ("workers-vmapped-several-tiles", 3, 2, 48, 16, 2, 2, 16, 32, 4),
          ("one-value-head-a-key-head", None, 1, 48, 16, 3, 3, 32, 16, 4),
          ("three-value-heads-a-key-head", None, 1, 32, 16, 1, 3, 16, 16, 4),
          ("two-taps", None, 1, 32, 8, 2, 4, 16, 16, 2),
          ("eight-taps", None, 1, 32, 8, 2, 4, 16, 16, 8),
          ("the-cells-widths", None, 1, 32, 16, 1, 2, 128, 128, 4)]


@pytest.mark.parametrize("case,workers,batch,length,tile,key_heads,value_heads,dk,dv,taps", SHAPES,
                         ids=[shape[0] for shape in SHAPES])
def test_kernel_is_todays_delta_heads(case, workers, batch, length, tile, key_heads, value_heads,
                                      dk, dv, taps):
    """q, k, v, z and the gradients of a seeded scalar of all four to the
    projection (z's lanes too) and to the taps, within 2e-6 of the XLA form's
    largest entry: float32 sums in another order, and a sigmoid whose division
    is two Newton steps."""
    cfg = config(key_heads, value_heads, dk, dv, taps)
    lead = ((workers,) if workers else ()) + (batch,)
    projected, weights_of_taps, weights = seeded(lead, length, cfg)
    theirs = lambda projected, taps: qwen3_next.split_heads(projected, taps, cfg)
    (_, outs), grads = scalar_and_gradients(kernel_form(cfg, tile), workers, weights)(
        projected, weights_of_taps)
    (_, ref_outs), ref_grads = scalar_and_gradients(theirs, workers, weights)(
        projected, weights_of_taps)
    for name, ours, wanted in zip(("q", "k", "v", "z", "dprojected", "dtaps"),
                                  outs + grads, ref_outs + ref_grads):
        assert ours.shape == wanted.shape, name
        assert gap(ours, wanted) < 2e-6, (name, gap(ours, wanted))
    np.testing.assert_array_equal(outs[3], ref_outs[3])     # z is a slice, untouched
    mixed = 2 * key_heads * dk + value_heads * dv           # z's cotangent lands in z's lanes
    np.testing.assert_array_equal(grads[0][..., mixed:], ref_grads[0][..., mixed:])


def test_a_tile_reads_its_halo_and_nothing_else():
    """Four tiles of 8: a position's q, k, v hang on the ``taps - 1`` positions
    before it ACROSS a tile's edge and on nothing after it; the first tile
    reads zeros before the sequence (a batch entry's first positions do not
    hang on the entry before); and in the backward pass a position's cotangent
    reaches the three positions before it across an edge, the last tile reading
    zeros after the sequence."""
    cfg = config(2, 4, 16, 16)
    projected, taps, _ = seeded((2,), 32, cfg, seed=5)
    operands = kernel_form(cfg, 8)
    base = operands(projected, taps)
    moved = operands(projected.at[0, 7].add(1.0), taps)     # the last position of the first tile
    for ours, was in zip(moved[:3], base[:3]):
        differs = np.abs(np.asarray(ours - was)).max(axis=(2, 3))
        assert (differs[0, :7] == 0).all() and (differs[0, 7:11] > 0).all()
        assert (differs[0, 11:] == 0).all() and (differs[1] == 0).all()
    # a cotangent at position 8 alone (the second tile's first) reaches positions 5..8
    weight = jnp.zeros_like(base[2]).at[0, 8].set(1.0)
    dprojected = jax.grad(lambda p: jnp.sum(operands(p, taps)[2] * weight))(projected)
    reached = np.abs(np.asarray(dprojected)).max(axis=2)
    assert (reached[0, 5:9] > 0).all() and (reached[0, :5] == 0).all()
    assert (reached[0, 9:] == 0).all() and (reached[1] == 0).all()
    # ... and one at the sequence's last position stays inside the sequence
    weight = jnp.zeros_like(base[2]).at[0, 31].set(1.0)
    ours = jax.grad(lambda p: jnp.sum(operands(p, taps)[2] * weight))(projected)
    theirs = jax.grad(lambda p: jnp.sum(qwen3_next.split_heads(p, taps, cfg)[2] * weight))(
        projected)
    assert gap(ours, theirs) < 2e-6 and float(jnp.max(jnp.abs(ours[1]))) == 0.0


def test_a_saturated_gate_stays_finite():
    """Projection entries of -300 and 300 under taps of one: ``exp`` of the
    convolution's negated output overflows float32; q, k, v and both gradients
    are finite and the XLA form's."""
    cfg = config(2, 4, 16, 16)
    projected, _, weights = seeded((1,), 16, cfg, seed=21)
    mixed = 2 * cfg.key_heads * cfg.key_dim + cfg.value_heads * cfg.value_dim
    projected = projected.at[0, 4:8, :mixed:3].set(-300.0).at[0, 9:12, 1:mixed:3].set(300.0)
    taps = jnp.ones((mixed, cfg.conv))
    theirs = lambda projected, taps: qwen3_next.split_heads(projected, taps, cfg)
    (_, outs), grads = scalar_and_gradients(kernel_form(cfg, 8), None, weights)(projected, taps)
    (_, ref_outs), ref_grads = scalar_and_gradients(theirs, None, weights)(projected, taps)
    for ours, wanted in zip(outs + grads, ref_outs + ref_grads):
        assert bool(jnp.all(jnp.isfinite(ours))) and gap(ours, wanted) < 2e-6


def test_the_taps_gradient_is_summed_over_tiles_and_batch_entries():
    """The taps' gradient of a batch of two in four tiles each is the sum of the
    eight (batch entry, tile)'s own — nothing of the VMEM sums, which outlive a
    grid step, leaks from one batch entry to the next — and a worker's gradient
    is its own."""
    cfg = config(2, 2, 16, 16)
    projected, taps, weights = seeded((2, 2), 32, cfg, seed=9)
    operands = kernel_form(cfg, 8)
    of_taps = lambda fn, p, w: jax.grad(
        lambda t: sum(jnp.sum(out * wi) for out, wi in zip(fn(p, t), w)))(taps)
    by_worker = jax.vmap(lambda p, *w: of_taps(operands, p, w))(projected, *weights)
    for worker in range(2):
        alone = sum(of_taps(lambda p, t: qwen3_next.split_heads(p, t, cfg),
                            projected[worker, b:b + 1], [w[worker, b:b + 1] for w in weights])
                    for b in range(2))
        assert gap(by_worker[worker], alone) < 2e-6
    assert gap(by_worker[0], by_worker[1]) > 0.1


def test_the_chooser_answers_by_platform_and_shape(monkeypatch):
    """Off a TPU: the XLA form, whatever the shape.  On one (steered): the
    kernel at the cell's shape; the XLA form for a bfloat16 projection, heads
    that are not whole lanes, more taps than the halo holds and a ragged
    length.  The seam forces either, and refuses what no tile divides."""
    cell = (4096, 128, 128, 4, jnp.float32)
    assert gdn_operands.operands_form(*cell) == "xla"
    monkeypatch.setattr(gdn_operands.hw, "on_tpu", lambda: True)
    assert gdn_operands.operands_form(*cell) == "kernel"
    assert gdn_operands.tile_for(4096) == gdn_operands.TILE
    assert gdn_operands.tile_for(64) == 64                  # a shorter sequence: one tile
    assert gdn_operands.operands_form(4096, 256, 128, 8, "float32") == "kernel"
    for length, dk, dv, taps, dtype in [
            (4096, 128, 128, 4, jnp.bfloat16), (4096, 96, 128, 4, jnp.float32),
            (4096, 128, 64, 4, jnp.float32), (4096, 128, 128, 9, jnp.float32),
            (4100, 128, 128, 4, jnp.float32), (4096 + 64, 128, 128, 4, jnp.float32)]:
        assert gdn_operands.operands_form(length, dk, dv, taps, dtype) == "xla", (
            length, dk, dv, taps, dtype)
    monkeypatch.undo()
    with gdn_operands.forced_form("kernel"):
        assert gdn_operands.operands_form(16, 16, 16, 4, jnp.float32) == "kernel"
        for refused in [(20, 16, 16, 4, jnp.float32), (16, 16, 16, 4, jnp.bfloat16),
                        (16, 16, 16, 9, jnp.float32)]:
            with pytest.raises(ValueError, match="whole tiles"):
                gdn_operands.operands_form(*refused)
        with gdn_operands.forced_form("xla"):
            assert gdn_operands.operands_form(*cell) == "xla"
        assert gdn_operands.operands_form(*cell) == "kernel"
    assert gdn_operands.operands_form(*cell) == "xla"
    with pytest.raises(ValueError, match="'kernel' or 'xla'"):
        with gdn_operands.forced_form("pallas"):
            pass
    cfg = config(2, 4, 16, 16)
    projected, taps, _ = seeded((1,), 24, cfg)
    with pytest.raises(ValueError, match="whole tiles"):
        kernel_form(cfg, 16)(projected, taps)
    with pytest.raises(ValueError, match="2 keys \\+ 2 values"):
        kernel_form(cfg, 8)(projected[..., :-16], taps)


def tiny_layer(cfg, seed=13):
    """(normed inputs (1, L, hidden), one DeltaNet layer's leaves)."""
    key = jax.random.PRNGKey(seed)
    shapes = qwen3_next.run_shapes(cfg, qwen3_next.DELTA, 1)
    layer = {name: 0.3 * jax.random.normal(jax.random.fold_in(key, place), shape[1:])
             for place, (name, shape) in enumerate(sorted(shapes.items()))}
    return jax.random.normal(jax.random.fold_in(key, 99), (1, cfg.seq, cfg.hidden)), layer


TINY = dict(hidden=32, chunk=16, seq=32, attn_chunk=16, experts=4, experts_per_token=2,
            expert_width=8, shared_width=8, experts_held=(0, 1), vocab=50, heads=2, kv_heads=1,
            head_dim=16)


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_the_models_entry_goes_where_the_chooser_says(form):
    """``qwen3_next.delta_heads`` — what ``gated_delta_net`` calls — hands
    ``split_heads``' result over off a TPU, a ragged length's too, and the
    kernel's inside the seam: a ``pallas_call`` of each name in the traced
    gradient, q, k, v, z of the shapes the delta rule's entry takes, g and beta
    untouched."""
    cfg = config(2, 4, 16, 16, **TINY)
    u, layer = tiny_layer(cfg)
    scalar = lambda u, layer: sum(jnp.sum(out) for out in qwen3_next.delta_heads(u, layer, cfg))
    with gdn_operands.forced_form(form):
        text = str(jax.make_jaxpr(jax.grad(scalar, argnums=(0, 1)))(u, layer))
        outs = qwen3_next.delta_heads(u, layer, cfg)
    assert ("gdn_operands_fwd" in text and "gdn_operands_bwd" in text) is (form == "kernel")
    wanted = qwen3_next.delta_heads(u, layer, cfg)          # off a TPU: the XLA form
    assert "gdn_operands" not in str(jax.make_jaxpr(scalar)(u, layer))
    for ours, theirs in zip(outs, wanted):
        assert ours.shape == theirs.shape and gap(ours, theirs) < 2e-6
    ragged = u[:, :27]
    with gdn_operands.forced_form("xla"):
        forced = qwen3_next.delta_heads(ragged, layer, cfg)
    for ours, theirs in zip(qwen3_next.delta_heads(ragged, layer, cfg), forced):
        np.testing.assert_array_equal(ours, theirs)


def test_a_layer_through_both_kernel_pairs_is_the_xla_layer():
    """``gated_delta_net`` with BOTH seams forced — the operands' kernels
    handing q, k, v to the delta rule's — against the layer in XLA: the
    output, and the gradients to the inputs and to every leaf of the mixer."""
    cfg = config(2, 4, 16, 16, **TINY)
    u, layer = tiny_layer(cfg, seed=17)
    weight = jax.random.normal(jax.random.PRNGKey(18), u.shape)

    def run():
        scalar = lambda u, layer: jnp.sum(qwen3_next.gated_delta_net(u, layer, cfg)[0] * weight)
        return jax.value_and_grad(scalar, argnums=(0, 1))(u, layer)

    with jax.default_matmul_precision("highest"):
        with gdn_operands.forced_form("kernel"), delta_rule.forced_form("kernel"):
            value, (du, dlayer) = run()
        ref_value, (ref_du, ref_dlayer) = run()
    assert abs(float(value - ref_value)) < 1e-4 * abs(float(ref_value))
    assert gap(du, ref_du) < 2e-5
    for name in ("w_qkvz", "w_ba", "conv", "A_log", "dt_bias", "o_norm", "wo"):
        assert gap(dlayer[name], ref_dlayer[name]) < 2e-5, name


def test_the_traced_kernel_step_holds_no_narrow_operand_and_no_product():
    """The entry and its backward pass at the cell's widths, traced with the
    kernel forced: grid/check.py's count of products with an operand narrower
    than float32 — the kernels' bodies included — reads 0; the kernels hold NO
    product at all (the count under a 64-bit threshold is the projections' and
    their gradients' alone, the same as the XLA form's), and nothing narrower
    than float32 is anywhere in them."""
    check = grid_module("check")
    cfg = config(1, 2, 128, 128, **TINY)
    u, layer = tiny_layer(cfg)
    scalar = lambda u, layer: sum(jnp.sum(out) for out in qwen3_next.delta_heads(u, layer, cfg))
    traced = lambda: jax.make_jaxpr(jax.grad(scalar, argnums=(0, 1)))(u, layer).jaxpr
    with gdn_operands.forced_form("kernel"):
        jaxpr = traced()
    assert check._count_narrow(jaxpr, 32) == 0
    assert check._count_narrow(jaxpr, 64) == check._count_narrow(traced(), 64) > 0

    def dtypes(jaxpr, inside=False):
        """Every dtype made inside the two kernels' bodies, their loops included."""
        for eqn in jaxpr.eqns:
            within = inside or str(eqn.params.get("name", "")).startswith("gdn_operands_")
            if within:
                yield from (var.aval.dtype for var in eqn.outvars)
            for inner in check._subjaxprs(eqn):
                yield from dtypes(inner, within)

    inside = set(dtypes(jaxpr))
    assert jnp.dtype(jnp.float32) in inside
    assert not any(jnp.issubdtype(d, jnp.floating) and jnp.finfo(d).bits < 32 for d in inside)


def test_the_check_scripts_column_runs_off_the_chip():
    """scripts/pallas_tpu_check.py ``--columns operands`` at a small size, the
    kernels interpreted: one row, six quantities at both precisions (no product
    in the kernels: the two agree), the bytes the kernels move, and a refusal
    to time an interpreter without being told to."""
    scripts = os.path.join(ROOT, "scripts")
    sys.path.insert(0, scripts)
    try:
        import pallas_tpu_check
    finally:
        sys.path.remove(scripts)
    rows = []
    failed = pallas_tpu_check.run_operands_check(
        reps=1, workers=2, length=32, key_heads=2, value_heads=4, width=16, taps=4,
        allow_interpret=True, emit=rows.append)
    assert failed == [] and len(rows) == 1 and rows[0]["parity"] == "ok", rows
    assert rows[0]["quantities"] == ["q", "k", "v", "z", "dprojected", "dtaps"]
    for precision in ("highest", "default"):
        assert len(rows[0]["gap_" + precision]) == 6 and max(rows[0]["gap_" + precision]) < 2e-6
    assert rows[0]["fwd_bytes"] > 0 and rows[0]["bwd_bytes"] > rows[0]["fwd_bytes"]
    with pytest.raises(RuntimeError, match="needs a TPU backend"):
        pallas_tpu_check.run_operands_check(reps=1)
