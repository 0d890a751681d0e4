"""The worker -> dimension reshard (parallel/engine.py ``_block_width``,
``_reshard_to_blocks``, ``_gather_blocks``): over W > 1 devices a column block
is as wide as the smallest multiple of the kernels' tile that holds
``ceil(d / W)`` columns, so every block starts on a lane boundary of the rows'
(8, 128) tiles and the cut in front of the ``all_to_all`` moves whole tiles.
The cut alone on the virtual CPU devices, and the four-chip program it compiles
to at ResNet-50's width for a described (not attached) ``v5e:2x2``.

Every program compiled for the described chip lives in this one file, config
2's in-step crop (models/preprocessing.py) included: only one process at a
time may load the TPU's library, and a second file's fixture could land on
another test worker and skip in silence.  The fused attention kernels
(ops/attention.py) at the shapes of the grid's Laguna cell are here for that
reason."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from aggregathor_tpu import gars
from aggregathor_tpu.ops.pallas_kernels import LANE, MAX_BLOCK
from aggregathor_tpu.parallel import RobustEngine, make_mesh
from aggregathor_tpu.parallel.mesh import worker_axis


def engine_over(devices, k, exchange=None):
    nb_workers = len(devices) * k
    return RobustEngine(make_mesh(nb_workers=len(devices), devices=devices),
                        gars.instantiate("average", nb_workers, 0), nb_workers,
                        exchange=exchange)


def cut_and_gather(engine, d):
    """``rows (n, d) -> (blocks (n, W * blk), gathered (n, d))`` through the
    engine's own cut and gather: the blocks side by side in device order, and
    every row of a block gathered the way the step gathers the aggregate."""

    def body(gvecs):
        block = engine._reshard_to_blocks(gvecs, d)
        return block, jax.vmap(lambda row: engine._gather_blocks(row, d))(block)

    return jax.jit(jax.shard_map(
        body, mesh=engine.mesh, in_specs=P(worker_axis),
        out_specs=(P(None, worker_axis), P()), check_vma=False))


@pytest.mark.parametrize("d,W,k,exchange", [
    (96, 8, 4, None),       # d divides, ceil(d / W) = 12, under one lane tile
    (100, 8, 1, None),      # the 100-parameter model on 8 devices: 13 -> 128, not 1,024
    (300, 2, 1, None),      # 150 -> 256
    (1000, 4, 8, None),     # 250 -> 256
    (1024, 2, 4, None),     # 512, on the boundary already: no padding at all
    (4096, 4, 1, None),     # 1,024 exactly
    (2049, 2, 8, None),     # 1,025 -> 2,048, d does not divide
    (5160, 4, 8, None),     # ResNet-50 on four chips scaled down: 10 x 128 + 10 -> 2,048
    (5157, 4, 8, None),     # the same block, d does not divide
    (8200, 8, 4, None),     # 1,025 -> 2,048 on 8 devices
    (5157, 4, 8, "bf16"),  # (16, 128) tiles on the wire: the cut must stay right
    (100, 8, 4, "bf16"),
    (100, 1, 8, None),      # one device: the identity
    (5160, 1, 4, None),
    (1290, 1, 1, "bf16"),
])
def test_block_cut(d, W, k, exchange):
    engine = engine_over(jax.devices()[:W], k, exchange)
    n, blk = W * k, engine._block_width(d)
    # whole numbers under 256: exact in bfloat16, and no two columns of a row
    # nor two rows of a column alike where it matters (251 and 241 are prime)
    rows = (np.arange(n)[:, None] * 241 + np.arange(d)[None, :]) % 251 + 1.0
    blocks, gathered = cut_and_gather(engine, d)(jnp.asarray(rows, jnp.float32))
    blocks, gathered = np.asarray(blocks, np.float32), np.asarray(gathered, np.float32)
    if W == 1:
        assert blk == d
    else:
        columns = -(-d // W)
        tile = MAX_BLOCK if columns >= MAX_BLOCK else LANE
        assert blk % tile == 0 and columns <= blk < columns + tile
    assert blocks.shape == (n, W * blk) and gathered.shape == (n, d)
    np.testing.assert_array_equal(blocks[:, :d], rows)   # every row's columns, in order
    np.testing.assert_array_equal(blocks[:, d:], 0.0)    # the padding is zeros
    np.testing.assert_array_equal(gathered, rows)


def test_gar_probe_times_the_steps_width():
    """The runner's ``gar_seconds_total`` probe aggregates rows as wide as the
    step's blocks."""
    engine = engine_over(jax.devices()[:4], 2)
    out = jax.block_until_ready(engine.build_gar_probe(d=5157)(0))
    assert out.shape == (4 * engine._block_width(5157),) == (4 * 2048,)


# --------------------------------------------------------------------------- #
# The four-chip program at ResNet-50's width, compiled for a described chip


@pytest.fixture(scope="module")
def v5e_2x2():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # whatever the missing or busy TPU library raises
        pytest.skip("no v5e:2x2 topology can be described here: %s" % exc)


def compile_uncached(jitted, *shapes):
    """A program compiled for a described chip is written to the persistent
    cache but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cache_api

    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cache_api.reset_cache()
    try:
        return jitted.lower(*shapes).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        cache_api.reset_cache()


def test_cut_is_a_bitcast_on_four_chips(v5e_2x2):
    """k = 8 rows of d = 25,557,032 over W = 4: the rows reach the
    ``all-to-all`` padded once and cut by a bitcast.  At a block of
    ``ceil(d / W)`` = 6,389,258 = 49,916 x 128 + 10 columns the compiler relaid
    every element through two ``while`` loops and a flat 204,456,256-vector,
    at twice the temporaries."""
    d, W, k = 25_557_032, 4, 8
    engine = engine_over(list(v5e_2x2.devices), k)
    blk = engine._block_width(d)
    assert blk == 6_389_760
    # six leaves, as a step's ``flatten`` concatenates them
    sizes = [9_408, 2_048_000, 1_000, 8_388_608, 4_194_304]
    sizes.append(d - sum(sizes))

    def body(*leaves):
        block = engine._reshard_to_blocks(jnp.concatenate(leaves, axis=1), d)
        return block, engine._gather_blocks(jnp.sum(block, axis=0), d)

    step = jax.jit(jax.shard_map(body, mesh=engine.mesh, in_specs=P(worker_axis),
                                 out_specs=(P(None, worker_axis), P()), check_vma=False))
    sharded = NamedSharding(engine.mesh, P(worker_axis))
    leaves = [jax.ShapeDtypeStruct((W * k, size), jnp.float32, sharding=sharded)
              for size in sizes]
    compiled = compile_uncached(step, *leaves)
    text = compiled.as_text()
    assert "while(" not in text
    operands = re.findall(r" all-to-all\(%?([\w.-]+)\)", text)
    assert len(operands) == 1
    producer, = re.findall(r"^ *%%?%s = .*$" % re.escape(operands[0]), text, re.M)
    assert " bitcast(" in producer and " copy(" not in producer, producer
    # the padded rows and nothing beside them (the blocks are the output)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.1 * k * W * blk * 4


@pytest.mark.parametrize("rule,n,f,d,kernel,held", [
    # the t = 16 selections, their mask (a pred[16, d]: 4 d bytes) and a row of room
    ("bulyan", 32, 7, 25_557_032, "coordinate_averaged_median_planes", (32 - 2 * 7 - 2) + 4 + 1),
    # nothing: the leftover columns land in the kernel's own output, in place
    ("median", 4, 1, 305_351_680, "coordinate_median_planes", 0.1),
])
def test_rule_reads_the_rows_as_they_are_on_one_chip(v5e_2x2, monkeypatch, rule, n, f, d, kernel, held):
    """Bulyan at W = 1 over ResNet-50's (32, 25,557,032) rows, 984 columns
    short of a block of 1,024, and the median over SDAR's (4, 305,351,680):
    the distance kernel reads the rows once, as one operand, and neither it
    nor the coordinate kernel behind it is handed a padded, copied or
    transposed matrix — the plane form takes each row out of the block as it
    lies — and what the coordinate kernel writes is its one dense row, not an
    (8, d) tile.  Until PR 30 each wrapper padded its rows to whole blocks
    first (3.27 + 1.64 GB copied a step, 15 ms of ``resnet50_bulyan_1chip``'s
    288); until PR 33 Bulyan's result left as 8 equal rows (0.82 GB a step)."""
    from jax.sharding import SingleDeviceSharding

    from aggregathor_tpu.gars.common import forced_tier
    from aggregathor_tpu.ops import pallas_kernels

    monkeypatch.setattr(pallas_kernels, "on_tpu", lambda: True)  # compile the kernels, not interpret
    rows = jax.ShapeDtypeStruct((n, d), jnp.float32,
                                sharding=SingleDeviceSharding(v5e_2x2.devices[0]))
    with forced_tier("pallas"):
        compiled = compile_uncached(jax.jit(gars.instantiate(rule, n, f).aggregate), rows)
    text = compiled.as_text()
    calls = dict(re.findall(r"^ *%?((?:pairwise|coordinate)[\w.-]*) = .* custom-call\(([^)]*)\), "
                            r'custom_call_target="tpu_custom_call"', text, re.M))
    assert all(len(operands.split(",")) == 1 for operands in calls.values()), calls
    selection, = [name for name in calls if name.startswith(kernel)]
    if rule == "bulyan":
        distance, = [name for name in calls if name.startswith("pairwise_sq_distances")]
        assert "grads" in calls[distance]
    else:
        assert len(calls) == 1 and "grads" in calls[selection]
    # nothing writes the n rows anew in front of a kernel, nor 8 rows behind
    # one (the entry computation's instructions are the ones that own a buffer)
    assert not re.search(r"= f32\[(%d|8),\d{7,}\]\S* (pad|copy|transpose|fusion)\(" % n,
                         text[text.index("\nENTRY "):])
    assert compiled.memory_analysis().temp_size_in_bytes < held * d * 4


def test_crop_neither_loops_nor_slices_on_the_chip(v5e_2x2):
    """Config 2's in-step crop as ``aug_one`` runs it, 8 workers x 1024
    ``bfloat16`` images: static shifts under selects compile to loop fusions.
    As a vmapped ``dynamic_slice`` it was a ``while`` of 8192 per-image
    ``dynamic-update-slice``s, a fifth of the step (PERF.md, PR 28)."""
    from jax.sharding import SingleDeviceSharding

    from aggregathor_tpu.models import preprocessing

    workers = 8
    transform = preprocessing.device_transform("cifarnet")

    def augment(images, key):
        def aug_one(worker_images, j):
            wkey = jax.random.fold_in(jax.random.fold_in(key, j), 3)
            return transform({"image": worker_images}, wkey)["image"]

        return jax.vmap(aug_one)(images, jnp.arange(workers))

    one_chip = SingleDeviceSharding(v5e_2x2.devices[0])
    compiled = compile_uncached(
        jax.jit(augment),
        jax.ShapeDtypeStruct((workers, 1024, 32, 32, 3), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip))
    text = compiled.as_text()
    assert " while(" not in text and " dynamic-update-slice(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 300e6


@pytest.mark.parametrize("workers,rep,mask,kv_heads,widths", [
    (3, 6, "full", 4, (128, 128)), (3, 8, "window", 4, (128, 128)), (4, 8, "block", 4, (128, 128)),
    (3, 1, "full", 16, (192, 128)), (3, 8, "selected", 4, (128, 128))],
    ids=["full", "window", "block-diffusion", "latent", "selected"])
def test_attention_kernels_compile_for_the_chip_at_the_cells_shapes(v5e_2x2, monkeypatch, workers,
                                                                    rep, mask, kv_heads, widths):
    """The fused attention kernel and its backward pass as the step of
    ``laguna_avgmedian_causal4k`` calls them — three workers under ``vmap``,
    L = 4096, 4 kv heads of 128 serving 6 (full) or 8 (window 512) query heads
    each — and as ``sdar30b_median_blockdiff``'s does — four workers, 8 query
    heads a kv head, models/sdar.py's predicate over [noisy ; clean] of 2,048
    each in blocks of 4, which has to lower inside the Mosaic kernel — and as
    ``kanana_avgmedian_causal4k``'s does — three workers, 16 heads each its own
    key head, scores over 192 and values of 128, each at its own width: two
    heads a grid step, blocks of 384 and 256 lanes cut out of q, k and v as
    they lie — compile for the described chip: the tiles fit VMEM, every slice
    is on a tile boundary, and what the two leave in HBM beside q, k, v, the
    output and their gradients is one log-sum-exp a query a head (128 lanes
    wide as the chip stores it) and a row-major copy of q: nothing
    score-shaped, nothing a fold, and at two widths no padded copy of q, k or
    v in and nothing 256 lanes a head out.  And as ``keye_avgmedian_sparse8k``'s
    does — three workers, L = 8192, the mask an int8 (L, L) operand a worker
    whose (256, L) rows of a query tile ride in VMEM beside a head's whole K, V,
    dk and dv at twice the other cells' length."""
    from jax.sharding import SingleDeviceSharding

    from aggregathor_tpu.models.sdar import BlockDiffusion
    from aggregathor_tpu.ops import attention

    monkeypatch.setattr(attention.hw, "on_tpu", lambda: True)  # compile the kernels, not interpret
    monkeypatch.setattr(attention, "info", lambda *_: None)
    length, (head_dim, v_dim) = 8192 if mask == "selected" else 4096, widths
    mask = {"full": attention.Causal(None), "window": attention.Causal(512),
            "block": BlockDiffusion(length // 2, 4), "selected": attention.Selected(2048)}[mask]
    assert attention.attention_form(length, head_dim, v_dim, kv_heads, rep) == "kernel"
    one_chip = SingleDeviceSharding(v5e_2x2.devices[0])
    shape = lambda *dims, dtype=jnp.float32: jax.ShapeDtypeStruct(
        (workers, 1, length) + dims, dtype, sharding=one_chip)
    by_data = isinstance(mask, attention.Selected)  # its pairs: one more operand
    pairs = (shape(length, dtype=jnp.int8),) if by_data else ()
    attend = jax.vmap(lambda q, k, v, *pairs: attention.attend(q, k, v, mask, None, *pairs))
    compiled = compile_uncached(
        jax.jit(jax.grad(lambda q, k, v, *pairs: jnp.sum(attend(q, k, v, *pairs) ** 2),
                         argnums=(0, 1, 2))),
        shape(kv_heads, rep, head_dim), shape(kv_heads, head_dim), shape(kv_heads, v_dim), *pairs)
    text = compiled.as_text()
    calls = re.findall(r"^ *%?([\w.-]*" + ("selected" if by_data else "causal")
                       + r"_attention_(?:fwd|bwd)[\w.-]*) = .* custom-call\(.*"
                       r'custom_call_target="tpu_custom_call"', text, re.M)
    assert len(calls) == 2 and any("fwd" in name for name in calls) and any(
        "bwd" in name for name in calls), calls
    assert " while(" not in text
    q_bytes = workers * length * kv_heads * rep * head_dim * 4
    # the log-sum-exp (128 lanes a query a head in HBM), q's copy, the output, its cotangent: 4 q's
    # at equal widths, and room; no more at two
    assert compiled.memory_analysis().temp_size_in_bytes < 5 * q_bytes
    if head_dim != v_dim:
        padded = kv_heads * rep * attention._whole_lanes(head_dim)
        assert " pad(" not in text and not re.search(r"f32\[[\d,]*\b(%d|%d)\]" % (
            padded, attention._whole_lanes(head_dim)), text)
        wide = lambda width: "f32[%d,1,%d,%d]" % (workers, length, kv_heads * rep * width)
        kernels = [line for line in text.splitlines() if "tpu_custom_call" in line]
        assert all(wide(v_dim) in line for line in kernels)     # the output; v's gradient
        assert any(line.count(wide(head_dim)) >= 2 for line in kernels)  # q's and k's


def test_the_select_kernel_compiles_for_the_chip_at_the_cells_shape(v5e_2x2, monkeypatch):
    """ops/select.py's threshold by counting as the step of
    ``keye_avgmedian_sparse8k`` calls it — three workers under ``vmap``, a chunk
    of 512 queries over 8,192 scores numbered by a traced index, k = 2,048 —
    compiles for the described chip: a tile's whole rows and their keys fit VMEM,
    every slice is on a tile boundary, the int8 pairs leave the kernel, and
    nothing is sorted."""
    from jax.sharding import SingleDeviceSharding

    from aggregathor_tpu.models import keye_vl2
    from aggregathor_tpu.ops import select

    monkeypatch.setattr(select.hw, "on_tpu", lambda: True)  # compile the kernel, not interpret
    monkeypatch.setattr(select, "info", lambda *_: None)
    workers, chunk, length, topk = 3, 512, 8192, 2048
    assert select.select_form(chunk, length, topk) == "kernel"
    one_chip = SingleDeviceSharding(v5e_2x2.devices[0])
    compiled = compile_uncached(
        jax.jit(lambda scores, number: jax.vmap(lambda scores: keye_vl2.top_keys(
            scores, number * chunk + jnp.arange(chunk), topk).astype(jnp.int8))(scores)),
        jax.ShapeDtypeStruct((workers, 1, chunk, length), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
    text = compiled.as_text()
    calls = re.findall(r"^ *(?:ROOT )?%?([\w.-]*select_threshold[\w.-]*) = s8\[3,1,512,8192\].* "
                       r'custom-call\(.*custom_call_target="tpu_custom_call"', text, re.M)
    assert len(calls) == 1, calls
    assert " sort(" not in text and " while(" not in text
    # beside the scores and the pairs: the pairs as booleans' int8 again at most
    assert compiled.memory_analysis().temp_size_in_bytes <= workers * chunk * length


def test_the_delta_rule_kernels_compile_for_the_chip_at_the_cells_shape(v5e_2x2, monkeypatch):
    """ops/delta_rule.py's kernel pair as the step of
    ``qwen3next_avgmedian_causal4k`` calls it — three workers under ``vmap``, L =
    4096 in chunks of 64, 32 heads of 128 by 128, through models/qwen3_next.py's
    entry — compiles for the described chip: a tile's blocks and the backward
    kernel's scratch fit VMEM, every slice is on a tile boundary, the masked
    sums and the transposed products lower.  What the pair leaves in HBM
    beside its operands and results is the state entering each chunk (64 KiB a
    chunk a head) and the row-major copies in and out; nothing chunk-shaped
    (``f32[3,1,64,64,32,128]``: eleven copies of 201 MB in the XLA form), and no
    scan."""
    from jax.sharding import SingleDeviceSharding

    from aggregathor_tpu.models import qwen3_next
    from aggregathor_tpu.ops import delta_rule

    monkeypatch.setattr(delta_rule.hw, "on_tpu", lambda: True)  # compile the kernels, not interpret
    monkeypatch.setattr(delta_rule, "info", lambda *_: None)
    workers, length, heads, chunk, width = 3, 4096, 32, 64, 128
    assert delta_rule.delta_rule_form(length, chunk, width, width) == "kernel"
    one_chip = SingleDeviceSharding(v5e_2x2.devices[0])
    shape = lambda *dims: jax.ShapeDtypeStruct((workers, 1, length, heads) + dims, jnp.float32,
                                               sharding=one_chip)
    rule = jax.vmap(lambda *args: qwen3_next.delta_rule(*args, chunk))

    def scalar(*args):
        out, state = rule(*args)
        return jnp.sum(out ** 2) + jnp.sum(state ** 2)

    compiled = compile_uncached(jax.jit(jax.grad(scalar, argnums=(0, 1, 2, 3, 4))),
                                shape(width), shape(width), shape(width), shape(), shape())
    text = compiled.as_text()
    calls = re.findall(r"^ *%?([\w.-]*delta_rule_(?:fwd|bwd)[\w.-]*) = .* custom-call\(.*"
                       r'custom_call_target="tpu_custom_call"', text, re.M)
    assert len(calls) == 2 and any("fwd" in name for name in calls) and any(
        "bwd" in name for name in calls), calls
    assert " while(" not in text and "f32[3,1,64,64,32,128]" not in text
    operand = workers * length * heads * width * 4
    states = workers * heads * (length // chunk) * width * width * 4
    # the kept states, the output's cotangent, and a row-major copy each of q, k, v and of the
    # three gradients the other way (parameters and results of THIS program lie head-major)
    assert compiled.memory_analysis().temp_size_in_bytes < states + 8 * operand


def test_the_operands_kernels_hand_the_delta_rules_their_blocks_on_the_chip(v5e_2x2, monkeypatch):
    """ops/gdn_operands.py's kernel pair as the step of
    ``qwen3next_avgmedian_causal4k`` calls it — three workers under ``vmap``
    with the taps shared, a projection of 1 x 4,096 x 12,288, 16 key heads and
    32 value heads of 128 lanes, through models/qwen3_next.py's ``delta_heads``
    INTO the delta rule's entry — compiles for the described chip: whole rows
    of the projection a tile and the backward kernel's scratch fit VMEM, the
    sublane rotations and the selects lower.  Between the two kernel pairs
    stands NOTHING: ``delta_rule_fwd`` reads ``gdn_operands_fwd``'s three
    results and ``gdn_operands_bwd`` reads ``delta_rule_bwd``'s three as they
    are (a ``get-tuple-element`` each: no ``copy``, no ``reshape``, no fusion),
    and no (3, 1, 4096, 8192) tensor — the XLA form's passes — is left."""
    from jax.sharding import SingleDeviceSharding

    from aggregathor_tpu.models import qwen3_next
    from aggregathor_tpu.ops import delta_rule, gdn_operands

    for module in (delta_rule, gdn_operands):   # compile the kernels, not interpret
        monkeypatch.setattr(module.hw, "on_tpu", lambda: True)
        monkeypatch.setattr(module, "info", lambda *_: None)
    cfg, workers = qwen3_next.Qwen3NextConfig(), 3
    assert gdn_operands.operands_form(cfg.seq, cfg.key_dim, cfg.value_dim, cfg.conv,
                                      cfg.dtype) == "kernel"
    one_chip = SingleDeviceSharding(v5e_2x2.devices[0])
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)
    shapes = qwen3_next.run_shapes(cfg, qwen3_next.DELTA, 1)
    layer = {name: shape(*dims[1:]) for name, dims in shapes.items()}

    def one_worker(u, layer):
        q, k, v, z, g, beta = qwen3_next.delta_heads(u, layer, cfg)
        out, _ = qwen3_next.delta_rule(q, k, v, g, beta, cfg.chunk)
        return jnp.sum(out * z)

    scalar = lambda u, layer: jnp.sum(jax.vmap(one_worker, in_axes=(0, None))(u, layer))
    compiled = compile_uncached(jax.jit(jax.grad(scalar, argnums=(0, 1))),
                                shape(workers, 1, cfg.seq, cfg.hidden), layer)
    text = compiled.as_text()
    call = lambda name: re.search(
        r"^ *%?([\w.-]*" + name + r"[\w.-]*) = .* custom-call\((.*?)\), "
        r'custom_call_target="tpu_custom_call"', text, re.M)
    calls = {name: call(name) for name in ("gdn_operands_fwd", "gdn_operands_bwd",
                                           "delta_rule_fwd", "delta_rule_bwd")}
    assert all(calls.values()), calls

    def results_of(name):
        """The names of ``name``'s results: its get-tuple-elements, by index."""
        found = re.findall(r"^ *(%[\w.-]+) = [^\n]* get-tuple-element\(%" + re.escape(
            calls[name].group(1)) + r"\), index=(\d)", text, re.M)
        return {int(index): result for result, index in found}

    operands = lambda name: [arg.strip() for arg in re.sub(
        r"/\*index=\d+\*/", "", calls[name].group(2)).split(",")]
    made, handed_back = results_of("gdn_operands_fwd"), results_of("delta_rule_bwd")
    assert operands("delta_rule_fwd")[:3] == [made[0], made[1], made[2]]
    # dq, dq's halo, dk, dk's halo, dv, dv's halo, after the projection thrice and the taps
    assert operands("gdn_operands_bwd")[4:10] == [handed_back[i] for i in (0, 0, 1, 1, 2, 2)]
    mixed = 2 * cfg.key_heads * cfg.key_dim + cfg.value_heads * cfg.value_dim
    assert "f32[%d,1,%d,%d]" % (workers, cfg.seq, mixed) not in text
