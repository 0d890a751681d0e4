"""ops/attention.py — the fused attention kernel — in interpreter mode on the
CPU, at small shapes: against one dense masked softmax (output and the
gradients of q, k and v), called as the step calls it (``vmap`` over workers
inside ``checkpoint`` inside ``scan``); the tile table against a brute-force
count of the mask; the chooser and its seam; the products the float32
Laguna and SDAR losses hold with the kernel forced, the kernel's body
included; and models/sdar.py's ``masked_attention`` through the kernel against
its own XLA form.
Products run at ``highest`` precision, so what separates kernel and reference
is the order of float32 sums.  (The kernels compiled for the described chip at
the cell's shapes: tests/test_reshard.py, where every such program lives.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aggregathor_tpu import models
from aggregathor_tpu.models import laguna, sdar
from aggregathor_tpu.ops import attention
from aggregathor_tpu.ops.attention import CLEAR, EDGED, SKIPPED, Causal, Selected
from aggregathor_tpu.models.sdar import BlockDiffusion

LENGTH, KV_HEADS, HEAD_DIM, WORKERS, LAYERS = 32, 2, 16, 3, 2


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


# models/sdar.py's mask: another predicate than Causal, whose rows of the tile table have gaps
# and two runs
MASKS = [Causal(None), Causal(12), Causal(20), Causal(5), BlockDiffusion(LENGTH // 2, 4)]
MASK_IDS = ["full", "window-12", "window-20", "window-5", "block-diffusion"]


def dense_attention(q, k, v, mask):
    """One softmax over all L keys under the boolean matrix."""
    index = jnp.arange(q.shape[1])
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) / np.sqrt(q.shape[-1])
    weights = jax.nn.softmax(jnp.where(mask(index[:, None], index[None, :]), scores, -jnp.inf),
                             axis=-1)
    return jnp.einsum("bgrqk,bkgd->bqgrd", weights, v).reshape(q.shape[0], q.shape[1], -1)


def stepped(attend):
    """sum over layers and workers of <attend(q, k, v), w>, computed the way
    the engine's step does: a scan over layers, each body checkpointed, the
    workers under ``vmap``."""
    def total(q, k, v, w):
        @jax.checkpoint
        def layer(carry, leaves):
            q, k, v, w = leaves
            return carry + jnp.sum(jax.vmap(attend)(q, k, v) * w), None

        return jax.lax.scan(layer, jnp.float32(0), (q, k, v, w))[0]

    return jax.jit(jax.value_and_grad(total, argnums=(0, 1, 2)))


def seeded(rep, dtype=jnp.float32, lead=(LAYERS, WORKERS, 1), widths=(HEAD_DIM, HEAD_DIM),
           kv_heads=KV_HEADS, seed=5):
    """q, k, v and a weight as wide as the output; ``widths`` the scores' and the values'."""
    key, (qk_dim, v_dim) = jax.random.PRNGKey(seed), widths
    normal = lambda place, *dims: jax.random.normal(
        jax.random.fold_in(key, place), lead + (LENGTH,) + dims).astype(dtype)
    return (normal(0, kv_heads, rep, qk_dim), normal(1, kv_heads, qk_dim),
            normal(2, kv_heads, v_dim), normal(3, kv_heads * rep * v_dim))


# a window of 12 cuts every tile of 8 it touches, 20 leaves one clear, 5 is under a tile
@pytest.mark.parametrize("tiles", [(32, 32), (8, 8), (16, 8)], ids=["one-tile", "8x8", "16x8"])
@pytest.mark.parametrize("rep", [6, 8])
@pytest.mark.parametrize("mask", MASKS, ids=MASK_IDS)
def test_kernel_is_a_dense_masked_softmax(mask, rep, tiles):
    q, k, v, w = seeded(rep)
    (ours, ours_grads), (theirs, theirs_grads) = (
        stepped(lambda q, k, v: attention.fused_attention(q, k, v, mask, *tiles))(q, k, v, w),
        stepped(lambda q, k, v: dense_attention(q, k, v, mask))(q, k, v, w))
    assert abs(float(ours) - float(theirs)) <= 1e-5 * abs(float(theirs))
    for mine, dense in zip(ours_grads, theirs_grads):
        assert mine.shape == dense.shape and mine.dtype == dense.dtype
        np.testing.assert_allclose(np.asarray(mine), np.asarray(dense), rtol=1e-4, atol=2e-5)
    out = jax.vmap(lambda q, k, v: attention.fused_attention(q, k, v, mask, *tiles))(q[0], k[0], v[0])
    np.testing.assert_allclose(np.asarray(out), np.asarray(jax.vmap(
        lambda q, k, v: dense_attention(q, k, v, mask))(q[0], k[0], v[0])), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mask,length,tiles,counts", [
    (Causal(None), 4096, (256, 256), (120, 16, 120)),   # a full layer of the grid's cell, a head
    (Causal(512), 4096, (256, 256), (15, 30, 211)),     # a window layer
    (Causal(512), 4096, (128, 512), None),
    (Causal(20), 32, (8, 8), None),
    (Causal(5), 32, (16, 8), None),
    (BlockDiffusion(2048, 4), 4096, (256, 256), (56, 24, 176)),  # models/sdar.py's, at its cell's shape
    (BlockDiffusion(16, 4), 32, (8, 8), None),
    (BlockDiffusion(2048, 8), 4096, (256, 256), (56, 24, 176)),  # the harness's planted fault
    (BlockDiffusion(18, 6), 36, (12, 6), None),                  # a block that is no power of two
])
def test_the_table_counts_what_the_mask_allows(mask, length, tiles, counts):
    """Every tile's class against the mask over its pairs (the models' own
    ``allowed``), the three counts at the cells' shapes, and the kernel's
    loops: each tile that is not SKIPPED in exactly one range, the even slots
    EDGED and the odd CLEAR."""
    q_tile, k_tile = tiles
    table = attention.tile_table(mask, length, q_tile, k_tile)
    index = np.arange(length)
    if isinstance(mask, Causal):
        ok = np.asarray(laguna.allowed(index, index, mask.window))
    else:
        position, noisy = index % mask.half, index < mask.half
        ok = np.asarray(sdar.allowed(position, noisy, position, noisy, mask.block))
    for i in range(length // q_tile):
        for j in range(length // k_tile):
            tile = ok[i * q_tile:(i + 1) * q_tile, j * k_tile:(j + 1) * k_tile]
            assert table[i, j] == (CLEAR if tile.all() else EDGED if tile.any() else SKIPPED)
    found = attention.table_counts(table)
    assert sum(found.values()) == table.size
    if counts:
        assert (found["clear"], found["edged"], found["skipped"]) == counts
    folded = np.zeros_like(table)
    for slot, (starts, stops) in enumerate(attention._slots(table)):
        for i, (start, stop) in enumerate(zip(starts, stops)):
            assert (table[i, start:stop] == (EDGED if slot % 2 == 0 else CLEAR)).all()
            folded[i, start:stop] += 1
    assert np.array_equal(folded, (table != SKIPPED).astype(folded.dtype))


def kernels_in(jaxpr):
    """Names of the ``pallas_call``s of a jaxpr, inner jaxprs included."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for inner in jax.core.jaxprs_in_params(eqn.params):
            names += kernels_in(inner)
    return names


def traced_kernels(length, head_dim=HEAD_DIM, chunk=8):
    cfg = laguna.LagunaConfig(seq=length, attn_chunk=chunk, head_dim=head_dim)
    shape = lambda *dims: jax.ShapeDtypeStruct((1, length) + dims, jnp.float32)
    return kernels_in(jax.make_jaxpr(lambda q, k, v: laguna.causal_attention(q, k, v, cfg, 12))(
        shape(2, 3, head_dim), shape(2, head_dim), shape(2, head_dim)).jaxpr)


def test_the_chooser_off_a_tpu_and_inside_the_seam():
    """Off a TPU the chunked form runs; inside the seam the kernel does, and
    leaves it again; a forced kernel refuses a length its tiles do not divide."""
    assert attention.attention_form(4096, 128) == "xla" and traced_kernels(LENGTH) == []
    with attention.forced_form("kernel"):
        assert attention.attention_form(4096, 128) == "kernel"
        assert traced_kernels(LENGTH) == ["causal_attention_fwd"]
        with attention.forced_form("xla"):
            assert traced_kernels(LENGTH) == []
        with pytest.raises(ValueError):
            attention.attention_form(4096 + 8, 128)
    assert attention.attention_form(4096, 128) == "xla"
    with pytest.raises(ValueError):
        with attention.forced_form("pallas"):
            pass


@pytest.mark.parametrize("length,head_dim,form", [
    (4096, 128, "kernel"),       # the grid's cell
    (256, 128, "kernel"),        # a sequence of one tile
    (4096 + 256, 128, "kernel"),
    (4096 + 8, 128, "xla"),      # a length the tiles do not divide
    (4096, 64, "xla"),           # a head that is not whole lanes
    (32768, 128, "xla"),         # a head's K and V would not stay in VMEM
])
def test_the_chooser_on_a_tpu_takes_the_shapes_the_kernel_takes(monkeypatch, length, head_dim, form):
    monkeypatch.setattr(attention.hw, "on_tpu", lambda: True)
    assert attention.attention_form(length, head_dim) == form


def test_a_length_that_does_not_divide_runs_the_chunked_form_on_a_tpu(monkeypatch):
    """What the step would trace on a TPU: the kernel at a length of whole
    tiles, the scan of chunks at one that is not (tiles shrunk to the test's
    size; the kernel is traced, not run)."""
    monkeypatch.setattr(attention.hw, "on_tpu", lambda: True)
    monkeypatch.setattr(attention, "Q_TILE", 16)
    monkeypatch.setattr(attention, "K_TILE", 16)
    monkeypatch.setattr(attention, "info", lambda *_: None)
    assert traced_kernels(LENGTH, head_dim=128) == ["causal_attention_fwd"]
    assert traced_kernels(LENGTH + 8, head_dim=128) == []


def products_of(jaxpr, inside=False):
    """[(operand dtypes, inside a kernel?)] of every product, the bodies of
    ``pallas_call``s included (grid/check.py ``_count_narrow`` walks the same
    way: a kernel's body is its ``jaxpr`` parameter)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("dot_general", "conv_general_dilated"):
            found.append((tuple(v.aval.dtype for v in eqn.invars), inside))
        for inner in jax.core.jaxprs_in_params(eqn.params):
            found += products_of(inner, inside or eqn.primitive.name == "pallas_call")
    return found


def test_the_float32_loss_with_the_kernel_forced_holds_no_narrow_product():
    """The float32 Laguna loss and its gradient, kernel forced: no product has
    an operand under 32 bits — the kernels' own seven a layer included — so
    ``narrow_products`` stays 0 in the grid's cell."""
    experiment = models.instantiate("laguna", [
        "vocab:50", "hidden:64", "kv-heads:2", "head-dim:16",
        "layer-types:full,sliding,sliding,sliding,full",
        "mlp-types:dense,sparse,sparse,sparse,sparse", "heads:6,8,8,8,6", "window:12",
        "dense-width:96", "experts:16", "experts-per-token:4", "expert-width:24",
        "shared-width:24", "experts-held:1,4,7,12", "seq:%d" % LENGTH, "attn-chunk:8",
        "batch-size:1", "corpus:4"])
    params = experiment.init(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.asarray(experiment.corpus[:1])}
    with attention.forced_form("kernel"):
        jaxpr = jax.make_jaxpr(jax.grad(lambda p: experiment.loss(p, batch)[0]))(params)
    products = products_of(jaxpr.jaxpr)
    in_kernels = [operands for operands, inside in products if inside]
    assert len(in_kernels) >= 2 + 5  # a forward's two and a backward's five, at the least
    assert all(jnp.finfo(dtype).bits >= 32 for operands, _ in products for dtype in operands)


@pytest.mark.parametrize("window", [None, 12])
def test_a_narrower_input_is_widened_inside_the_kernel(window):
    """bfloat16 q, k and v (``dtype:bfloat16``, the harness's control): every
    product inside the kernels takes float32 operands, the outputs come back
    as bfloat16 and the gradients are finite and near float32's."""
    mask, rep = Causal(window), 3
    q, k, v, w = seeded(rep, jnp.bfloat16, lead=(1,))
    value = lambda q, k, v: jnp.sum(
        attention.fused_attention(q, k, v, mask, 8, 8).astype(jnp.float32) * w.astype(jnp.float32))
    products = products_of(jax.make_jaxpr(jax.grad(value, argnums=(0, 1, 2)))(q, k, v).jaxpr)
    assert len(products) >= 2 + 5  # a forward's two and a backward's five, once a loop
    assert all(inside and operands == (jnp.float32, jnp.float32) for operands, inside in products)
    grads = jax.jit(jax.grad(value, argnums=(0, 1, 2)))(q, k, v)
    wide = jax.jit(jax.grad(lambda q, k, v: jnp.sum(dense_attention(q, k, v, mask) * w),
                            argnums=(0, 1, 2)))(*(a.astype(jnp.float32) for a in (q, k, v)))
    for narrow, exact in zip(grads, wide):
        assert narrow.dtype == jnp.bfloat16
        assert bool(jnp.all(jnp.isfinite(narrow.astype(jnp.float32))))
        scale = float(jnp.max(jnp.abs(exact)))
        assert float(jnp.max(jnp.abs(narrow.astype(jnp.float32) - exact))) <= 2e-2 * scale


SDAR_ARGS = ["vocab:50", "hidden:64", "heads:16", "kv-heads:2", "head-dim:16", "layers:2",
             "experts:16", "experts-per-token:4", "expert-width:24", "experts-held:1,4,7,12",
             "seq:%d" % (LENGTH // 2), "block:4", "attn-chunk:8", "batch-size:1", "corpus:4"]


@pytest.mark.parametrize("tile", [LENGTH, 8], ids=["one-tile", "8x8"])
@pytest.mark.parametrize("block", [4, 2, 8])
def test_sdar_attention_through_the_kernel_is_its_own_xla_form(monkeypatch, block, tile):
    """``sdar.masked_attention`` traced inside ``forced_form("kernel")``
    (interpreted) against the same call inside ``forced_form("xla")`` — the
    chunked form the CPU runs: output and the gradients of q, k and v, under
    ``vmap`` + ``checkpoint`` + ``scan`` as the step calls it; [noisy ; clean]
    of 16 positions each, 8 query heads a kv head."""
    monkeypatch.setattr(attention, "Q_TILE", tile)
    monkeypatch.setattr(attention, "K_TILE", tile)
    cfg = sdar.SdarConfig(seq=LENGTH // 2, block=block, attn_chunk=8, heads=16, kv_heads=KV_HEADS,
                          head_dim=HEAD_DIM).check()
    q, k, v, w = seeded(8)

    def under(form):
        def attend(q, k, v):
            with attention.forced_form(form):
                return sdar.masked_attention(q, k, v, cfg)
        return attend

    assert kernels_in(jax.make_jaxpr(under("kernel"))(q[0, 0], k[0, 0], v[0, 0]).jaxpr) == [
        "causal_attention_fwd"]
    (ours, ours_grads), (theirs, theirs_grads) = (stepped(under("kernel"))(q, k, v, w),
                                                  stepped(under("xla"))(q, k, v, w))
    assert abs(float(ours) - float(theirs)) <= 1e-5 * abs(float(theirs))
    for mine, chunked in zip(ours_grads, theirs_grads):
        assert mine.shape == chunked.shape and mine.dtype == chunked.dtype
        np.testing.assert_allclose(np.asarray(mine), np.asarray(chunked), rtol=1e-4, atol=2e-5)
    out = jax.vmap(under("kernel"))(q[0], k[0], v[0])
    np.testing.assert_allclose(np.asarray(out), np.asarray(jax.vmap(under("xla"))(q[0], k[0], v[0])),
                               rtol=1e-5, atol=1e-5)


def test_the_float32_sdar_loss_with_the_kernel_forced_holds_no_narrow_product():
    """As for Laguna: ``narrow_products`` stays 0 in ``sdar30b_median_blockdiff``
    with its attention on the kernel."""
    experiment = models.instantiate("sdar", SDAR_ARGS)
    params = experiment.init(jax.random.PRNGKey(0))
    batch = experiment.device_transform()({"tokens": jnp.asarray(experiment.corpus[:1])},
                                          jax.random.PRNGKey(1))
    with attention.forced_form("kernel"):
        jaxpr = jax.make_jaxpr(jax.grad(lambda p: experiment.loss(p, batch)[0]))(params)
    assert {"causal_attention_fwd", "causal_attention_bwd"} <= set(kernels_in(jaxpr.jaxpr))
    products = products_of(jaxpr.jaxpr)
    assert len([operands for operands, inside in products if inside]) >= 2 + 5
    assert all(jnp.finfo(dtype).bits >= 32 for operands, _ in products for dtype in operands)


@pytest.mark.parametrize("name", ["full", "window", "block-diffusion", "latent", "selected"])
def test_the_check_scripts_attention_column_runs_interpreted(name):
    """scripts/pallas_tpu_check.py ``run_attention_check`` — the chip's parity
    and timing of the kernel against each model's XLA form — off a TPU at a
    small size: a row a shape with the mask's name and parity ``ok``, a row a
    swept tile (the times mean nothing here)."""
    import os
    import sys

    scripts = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts")
    sys.path.insert(0, scripts)
    try:
        import pallas_tpu_check
    finally:
        sys.path.remove(scripts)
    rows = []
    shapes = [shape for shape in pallas_tpu_check.ATTENTION_SHAPES if shape[0] == name]
    failed = pallas_tpu_check.run_attention_check(
        reps=1, tiles=((8, 8),), length=LENGTH, kv_heads=KV_HEADS, head_dim=HEAD_DIM,
        shapes=shapes, allow_interpret=True, emit=rows.append)
    sys.modules.pop("pallas_tpu_check", None)
    assert failed == [] and [row["rule"] for row in rows] == [
        "attention-" + name, "attention-%s-tiles" % name]
    assert rows[0]["parity"] == "ok" and rows[0]["workers"] == shapes[0][1]
    assert rows[0]["mask"] == {"full": "Causal(window=None)", "window": "Causal(window=512)",
                               "block-diffusion": "BlockDiffusion(half=16, block=4)",
                               "latent": "Causal(window=None)",
                               "selected": "Selected(k=16)"}[name]   # twice the length: 64 // 4
    assert "kernel_fwd_bwd_ms" in rows[1] and "error" not in rows[1]
    with pytest.raises(RuntimeError):
        pallas_tpu_check.run_attention_check(reps=1, length=LENGTH, shapes=shapes)


# --------------------------------------------------------------------------- #
#  Two widths: scores wider than values (models/deepseek_v3.py)               #
# --------------------------------------------------------------------------- #


def naive_attention(q, k, v):
    """``dense_attention`` under the causal mask: its scale is q's true width's
    and its output as wide as v."""
    return dense_attention(q, k, v, Causal())


def equations_of(jaxpr):
    """Every equation of a jaxpr, inner jaxprs included, kernels' bodies not."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for inner in jax.core.jaxprs_in_params(eqn.params):
                yield from equations_of(inner)


def assert_a_naive_softmax(attend, q, k, v, w):
    """Output and the gradients of q, k and v against the naive softmax, under
    ``vmap`` + ``checkpoint`` + ``scan`` as the step calls it."""
    (ours, ours_grads), (theirs, theirs_grads) = (stepped(attend)(q, k, v, w),
                                                  stepped(naive_attention)(q, k, v, w))
    assert abs(float(ours) - float(theirs)) <= 1e-5 * abs(float(theirs))
    for mine, naive in zip(ours_grads, theirs_grads):
        assert mine.shape == naive.shape and mine.dtype == naive.dtype
        np.testing.assert_allclose(np.asarray(mine), np.asarray(naive), rtol=1e-4, atol=2e-5)
    out = jax.vmap(attend)(q[0], k[0], v[0])
    assert out.shape == w.shape[1:]
    np.testing.assert_allclose(np.asarray(out), np.asarray(jax.vmap(naive_attention)(q[0], k[0], v[0])),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("form", ["kernel", "xla"])
@pytest.mark.parametrize("widths,rep", [((192, 128), 1), ((24, 16), 3)],
                         ids=["192-over-128", "24-over-16-grouped"])
def test_two_widths_are_a_naive_softmax(widths, rep, form):
    """q and k of 192, v of 128 (latent attention's widths; and a small pair
    with grouped queries) through ``attend``: the interpreted kernel at the two
    widths as they are (192 / 128: the two key heads in one grid step, each
    over its span of 256 of the block's 384 lanes; 24 / 16: a head a step), and
    models/laguna.py's ``chunked_attention`` with its accumulator as wide as v,
    against the naive softmax.  The scale is ``1 / sqrt(192)``.  Nothing is
    padded in front of the kernel and what it writes is as wide as v."""
    qk_dim, v_dim = widths
    q, k, v, w = seeded(rep, widths=widths, seed=9)
    cfg = laguna.LagunaConfig(seq=LENGTH, attn_chunk=8)

    def attend(q, k, v):
        with attention.forced_form(form):
            return attention.attend(q, k, v, Causal(),
                                    lambda q, k, v: laguna.chunked_attention(q, k, v, cfg, None))

    traced = jax.make_jaxpr(attend)(q[0, 0], k[0, 0], v[0, 0]).jaxpr
    assert kernels_in(traced) == (["causal_attention_fwd"] if form == "kernel" else [])
    if form == "kernel":
        equations = list(equations_of(jax.make_jaxpr(jax.grad(
            lambda q, k, v: jnp.sum(attend(q, k, v)), argnums=(0, 1, 2)))(
                q[0, 0], k[0, 0], v[0, 0]).jaxpr))
        assert not [eqn for eqn in equations if eqn.primitive.name in ("pad", "slice")]
        wide = {eqn.params["name"]: [out.aval.shape[-1] for out in eqn.outvars]
                for eqn in equations if eqn.primitive.name == "pallas_call"}
        assert wide["causal_attention_fwd"][0] == KV_HEADS * rep * v_dim
        assert wide["causal_attention_bwd"] == [KV_HEADS * rep * qk_dim, KV_HEADS * qk_dim,
                                                KV_HEADS * v_dim]
    assert_a_naive_softmax(attend, q, k, v, w)


@pytest.mark.parametrize("on_tpu", [False, True], ids=["forced-kernel", "as-the-chooser-says"])
def test_an_odd_head_count_at_two_widths_is_a_naive_softmax(monkeypatch, on_tpu):
    """Three key heads of 192 over 128 divide into no block of whole lanes:
    forced (interpreted), the kernel takes them a head a step; on a TPU the
    chooser says ``xla`` — as for a second query head a key head — and
    ``attend`` traces the caller's form.  Either way, the naive softmax."""
    kv_heads, (qk_dim, v_dim) = 3, (192, 128)
    assert attention._heads_a_step(kv_heads, qk_dim, v_dim) == 1
    q, k, v, w = seeded(1, widths=(qk_dim, v_dim), kv_heads=kv_heads, seed=9)
    cfg = laguna.LagunaConfig(seq=LENGTH, attn_chunk=8)
    chunked = lambda q, k, v: laguna.chunked_attention(q, k, v, cfg, None)
    if on_tpu:
        monkeypatch.setattr(attention.hw, "on_tpu", lambda: True)
        monkeypatch.setattr(attention, "info", lambda *_: None)
        assert attention.attention_form(4096, qk_dim, v_dim, kv_heads, 1) == "xla"
        assert attention.attention_form(4096, qk_dim, v_dim, 16, 2) == "xla"
        assert attention.attention_form(4096, qk_dim, v_dim, 16, 1) == "kernel"
        attend = lambda q, k, v: attention.attend(q, k, v, Causal(), chunked)
    else:
        def attend(q, k, v):
            with attention.forced_form("kernel"):
                return attention.attend(q, k, v, Causal(), chunked)
    traced = kernels_in(jax.make_jaxpr(attend)(q[0, 0], k[0, 0], v[0, 0]).jaxpr)
    assert traced == ([] if on_tpu else ["causal_attention_fwd"])
    assert_a_naive_softmax(attend, q, k, v, w)


@pytest.mark.parametrize("length,head_dim,v_dim,kv_heads,form", [
    (4096, 192, 128, 16, "kernel"),   # the grid's Kanana cell: two heads a step, 4096 x (256 + 128)
    (4096, 192, 192, 16, "xla"),      # values that are not whole lanes
    (5632, 192, 128, 16, "xla"),      # 5632 x (256 + 128): a head's K and V would not stay in VMEM
    (4096, 128, 64, 16, "xla"),       # values that are not whole lanes, under scores that are
    (4096, 128, 128, 4, "kernel"),    # equal widths of whole lanes: as before
])
def test_the_chooser_admits_two_widths_by_what_is_resident(monkeypatch, length, head_dim, v_dim,
                                                           kv_heads, form):
    monkeypatch.setattr(attention.hw, "on_tpu", lambda: True)
    assert attention.attention_form(length, head_dim, v_dim, kv_heads) == form
    # equal widths: the rule they had, to the number
    assert attention.attention_form(8192, 128) == "kernel"
    assert attention.attention_form(8192 + 256, 128) == "xla"
    assert attention.attention_form(5376, 192, 128, 16) == "kernel"   # 5376 x 384 <= 2 x RESIDENT_MAX
    # heads a grid step, and the whole lanes a head's scores run over inside the block
    assert attention._heads_a_step(16, 192, 128) == 2 and attention._heads_a_step(4, 128, 128) == 1
    assert attention._span(0, 192, 2) == (0, 256, 0) and attention._span(1, 192, 2) == (128, 256, 64)
    assert attention._span(3, 128, 1) == (384, 128, 0) and attention._span(1, 24, 1) == (24, 24, 0)
    # tiles: 512 where a key head serves one query head and the length divides, else 256
    assert attention.tiles_for(4096, 1) == (512, 512) and attention.tiles_for(4096, 6) == (256, 256)
    assert attention.tiles_for(4096 + 256, 1) == (256, 256) and attention.tiles_for(32, 1) == (32, 32)


#: sha256 (first 16 hex digits) of the jaxpr of loss-and-gradient of the tiny float32 Laguna and
#: SDAR experiments below, captured ONCE at PR 48 (the tree on top of commit ee56f59, PR 47), when
#: ``ops/attention.py`` came down to one ``custom_vjp`` and one way to say a lone head's rows.  The
#: ``xla`` values are those read at PR 40's parent; the ``kernel`` texts differ from ee56f59's
#: (0e5667dacc0f1d38, 4f34e6666579e587) in one spelling only: a whole block of the log-sum-exp and
#: of ``dq`` is read and written as ``[:,:]`` where it was ``[...]`` (9 lines of Laguna's text, 3
#: of SDAR's; CHANGES.md, PR 48).
PARENT_JAXPRS = {("laguna", "kernel"): "d7c55fd8440a7e01", ("laguna", "xla"): "9ff6822bddfffb14",
                 ("sdar", "kernel"): "59a7b47f6f7d47c7", ("sdar", "xla"): "1ccb145e6db057f4"}
LAGUNA_ARGS = ["vocab:50", "hidden:64", "kv-heads:2", "head-dim:16",
               "layer-types:full,sliding,sliding,sliding,full",
               "mlp-types:dense,sparse,sparse,sparse,sparse", "heads:6,8,8,8,6", "window:12",
               "dense-width:96", "experts:16", "experts-per-token:4", "expert-width:24",
               "shared-width:24", "experts-held:1,4,7,12", "seq:%d" % LENGTH, "attn-chunk:8",
               "batch-size:1", "corpus:4"]


@pytest.mark.parametrize("name,form", sorted(PARENT_JAXPRS))
def test_equal_widths_trace_the_program_they_traced(name, form):
    """Laguna's and SDAR's steps are untouched by the two-width route and by
    the mask that is data: the jaxpr of each one's loss and gradient, the
    kernels' bodies included where the kernel is forced, is to the byte what
    was captured at PR 48 on top of commit ee56f59 (``PARENT_JAXPRS``; the
    text carries no file and no line; models/laguna.py's helpers that
    models/deepseek_v3.py shares trace to the same equations)."""
    import hashlib

    experiment = models.instantiate(name, LAGUNA_ARGS if name == "laguna" else SDAR_ARGS)
    params = experiment.init(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.asarray(experiment.corpus[:1])}
    if experiment.device_transform() is not None:
        batch = experiment.device_transform()(batch, jax.random.PRNGKey(1))
    with jax.default_matmul_precision("default"), attention.forced_form(form):
        text = str(jax.make_jaxpr(jax.value_and_grad(
            lambda p: experiment.loss(p, batch), has_aux=True))(params))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENT_JAXPRS[name, form]


# --------------------------------------------------------------------------- #
#  A mask that is data (models/keye_vl2.py)                                   #
# --------------------------------------------------------------------------- #


def seeded_pairs(keys, empty=()):
    """(LAYERS, WORKERS, 1, L, L) int8: every query reads ``keys`` seeded keys up
    to its own (all of them while there are no more), a selection a layer a
    worker; each (query tile, key tile) of 8 x 8 in ``empty`` is cleared whole,
    its queries reading what else they chose."""
    rng = np.random.default_rng(7)
    scores = np.where(np.tril(np.ones((LENGTH, LENGTH), bool)),
                      rng.random((LAYERS, WORKERS, 1, LENGTH, LENGTH)), -1.0)
    rank = np.argsort(np.argsort(-scores, axis=-1, kind="stable"), axis=-1)
    pairs = (rank < keys) & (scores >= 0)
    for i, j in empty:
        pairs[..., 8 * i:8 * i + 8, 8 * j:8 * j + 8] = False
    assert pairs.any(axis=-1).all()   # every query still reads some key
    return jnp.asarray(pairs.astype(np.int8))


def dense_by_pairs(q, k, v, pairs):
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) / np.sqrt(q.shape[-1])
    weights = jax.nn.softmax(jnp.where(pairs[:, None, None] != 0, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bgrqk,bkgd->bqgrd", weights, v).reshape(q.shape[0], q.shape[1], -1)


def stepped_by_pairs(attend):
    """``stepped`` with a selection a layer a worker beside q, k and v."""
    def total(q, k, v, w, pairs):
        @jax.checkpoint
        def layer(carry, leaves):
            q, k, v, w, pairs = leaves
            return carry + jnp.sum(jax.vmap(attend)(q, k, v, pairs) * w), None

        return jax.lax.scan(layer, jnp.float32(0), (q, k, v, w, pairs))[0]

    return jax.jit(jax.value_and_grad(total, argnums=(0, 1, 2)))


@pytest.mark.parametrize("tiles", [(32, 32), (8, 8), (16, 8)], ids=["one-tile", "8x8", "16x8"])
@pytest.mark.parametrize("keys,empty", [(6, ()), (6, ((2, 1), (3, 0), (3, 3))), (LENGTH, ())],
                         ids=["six-keys", "empty-tiles", "all-keys"])
def test_kernel_under_a_mask_that_is_data_is_a_dense_masked_softmax(keys, empty, tiles):
    """``Selected``: the allowed pairs arrive as an operand.  Output and the
    gradients of q, k and v against one dense softmax under the same pairs and
    against models/keye_vl2.py's chunked XLA form, as the step calls it; with
    whole key tiles that a query tile selected nothing of — the diagonal one
    among them — and with every causal key selected (``Causal()``'s result)."""
    from aggregathor_tpu.models import keye_vl2

    q, k, v, w = seeded(8)
    pairs = seeded_pairs(keys, empty)
    mask = Selected(keys)
    cfg = keye_vl2.KeyeVL2Config(seq=LENGTH, attn_chunk=8, head_dim=HEAD_DIM, kv_heads=KV_HEADS)
    ours, ours_grads = stepped_by_pairs(lambda q, k, v, pairs: attention.fused_attention(
        q, k, v, mask, *tiles, pairs=pairs))(q, k, v, w, pairs)
    for theirs, theirs_grads in (
            stepped_by_pairs(dense_by_pairs)(q, k, v, w, pairs),
            stepped_by_pairs(lambda q, k, v, pairs: keye_vl2.chunked_attention(
                q, k, v, pairs, cfg))(q, k, v, w, pairs)):
        assert abs(float(ours) - float(theirs)) <= 1e-5 * abs(float(theirs))
        for mine, dense in zip(ours_grads, theirs_grads):
            assert mine.shape == dense.shape and mine.dtype == dense.dtype
            np.testing.assert_allclose(np.asarray(mine), np.asarray(dense), rtol=1e-4, atol=2e-5)
    if keys == LENGTH:
        causal, causal_grads = stepped(lambda q, k, v: attention.fused_attention(
            q, k, v, Causal(), *tiles))(q, k, v, w)
        assert abs(float(ours) - float(causal)) <= 1e-5 * abs(float(causal))
        for mine, predicate in zip(ours_grads, causal_grads):
            np.testing.assert_allclose(np.asarray(mine), np.asarray(predicate), rtol=1e-4, atol=2e-5)


def test_a_mask_that_is_data_has_the_causal_table_with_every_tile_edged():
    """``Selected``'s tile table is ``Causal()``'s with no tile CLEAR (below the
    diagonal only the data says which pairs are allowed): at the cell's shape
    528 edged and 496 skipped, one loop a kernel; its two calls carry names of
    their own, the predicates' theirs; and a predicate with pairs, or
    ``Selected`` without, is refused."""
    table = attention.tile_table(Selected(2048), 8192, 256, 256)
    causal = attention.tile_table(Causal(), 8192, 256, 256)
    assert np.array_equal(table == SKIPPED, causal == SKIPPED) and not (table == CLEAR).any()
    assert attention.table_counts(table) == {"clear": 0, "edged": 528, "skipped": 496}
    assert len(attention._slots(table)) == 1
    assert attention.attention_form(8192, 128, 128, 4, 8) == "xla"   # off a TPU
    q, k, v, _ = seeded(2, lead=(1,))
    pairs = seeded_pairs(6)[0, 0]
    grad = lambda mask, pairs: jax.make_jaxpr(jax.grad(lambda q: jnp.sum(
        attention.fused_attention(q, k, v, mask, 8, 8, pairs=pairs))))(q).jaxpr
    assert kernels_in(grad(Selected(6), pairs)) == ["selected_attention_fwd",
                                                    "selected_attention_bwd"]
    assert kernels_in(grad(Causal(), None)) == ["causal_attention_fwd", "causal_attention_bwd"]
    for mask, given in ((Causal(), pairs), (Selected(6), None)):
        with pytest.raises(ValueError, match="pairs"):
            attention.fused_attention(q, k, v, mask, 8, 8, pairs=given)
