"""A Gated DeltaNet layer's operands in one pass: from the projection's output
to the blocks ops/delta_rule.py's kernels read, one Pallas kernel pair.

What models/qwen3_next.py's ``split_heads`` does in XLA between ``u @ w_qkvz``
and the delta rule's q, k, v — the ``split``, the causal depthwise convolution
as a pad and one shifted multiply a tap, SiLU, the cast, a second ``split``,
two L2 norms with their reductions, the ``repeat`` of the key heads to the
value heads, each a pass of its own over a (B, L, 2 keys + values) float32
tensor, and all their gradients — done here from VMEM, ONE read of the
projection and ONE write of each operand:

- **Forward** (``gdn_operands_fwd``): a grid step a (batch, tile of ``TILE``
  positions).  It reads the first ``2 keys + values`` lanes of the projection's
  output AS IT LIES (no ``split`` copy: z stays where it is and is handed on
  as the slice it is) with the 8 positions before the tile as a second block of
  the same array (the ``taps - 1`` last of them are the convolution's halo;
  zeros before the sequence), and walks the tile ``ROWS`` positions at a time,
  ``BLOCK`` lanes after ``BLOCK`` lanes: the taps (a shift is one select between
  a group and the group before it and one sublane rotation), ``x * sigmoid(x)``,
  and for a q or k head the sum of squares over the head's lanes, ``rsqrt(. +
  eps)``, q's ``key_dim ** -0.5``.
  q and k leave at the VALUE heads' width (a key head's block stored ``rep``
  times), v as it is: (B, L, H * D) arrays, the delta rule kernel's blocks.
- **Backward** (``gdn_operands_bwd``): reads the projection again (the 8
  positions before AND after the tile), the taps and dq, dk, dv as the delta
  rule's backward kernel wrote them (with the 8 positions after the tile), and
  z's cotangent.  The convolution's output and SiLU are made again — nothing
  of them is kept: the residuals are the projection and the taps, which the
  step holds anyway.  The two (``rep``) value heads of a key head are summed as
  dq and dk are loaded.  The cotangent of the convolution's output is made for
  the tile and for the ``taps - 1`` positions after it (zeros after the
  sequence), the cotangent of the projection is the taps over THOSE, and it
  leaves whole — z's lanes filled from z's cotangent, so that no ``pad``, ``add``
  or ``concatenate`` stands between this kernel and the projection's own
  gradient products.  The taps' gradient is summed over a batch entry's tiles
  in VMEM (the tiles of a batch entry one after another) and written at the
  last.

**The same arithmetic as the XLA form, and no less**: float32 in, float32
vector work throughout, no product at all; the taps summed in the XLA form's
order; the sums of squares over a head's lanes in float32; the one division,
SiLU's, is the unit's reciprocal estimate and two Newton steps (``_sigmoid``).

**One chooser** (``operands_form``): on a TPU, where the projection is float32,
the heads are whole lanes, the taps fit the halo and the length is whole tiles,
the kernel; else the caller's XLA form.  No flag and no environment variable;
``forced_form`` is the one scoped seam, for the tests and
scripts/pallas_tpu_check.py.  Off a TPU a forced kernel interprets.
"""

import contextlib
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import hw, info
from .pallas_kernels import LANE

#: Positions a tile (a grid step): the blocks are whole rows of the projection,
#: 32 KiB a position in and 48 out at the cell's widths.
TILE = 128

#: Positions walked at a time inside a tile, and positions a halo block: one
#: float32 sublane tile, so a shift is one rotation of it and the taps are at
#: most ``ROWS``.  Every head's chain of one such group is independent of every
#: other's, which is what fills the units' waits.
ROWS = 8

#: Lanes walked at a time: the elementwise work of so many lanes is ONE traced
#: operation each (eight vregs a value at 8 positions), the heads' norms a
#: slice each.  A head at a time is the same machine code from six times the
#: equations to trace and lower (14,229 for 2,223, forward and backward), three
#: kernels a step; twice as many lanes spill a tenth more bundles.
BLOCK = 1024

#: The largest exponent ``_sigmoid`` hands ``exp``: e^80 = 5.5e34 is finite in
#: float32 and its reciprocal is no denormal.
EXP_MAX = 80.0

#: What the compiler may use of VMEM (v5e: 128 MiB a core): the backward kernel
#: holds ~44 MB at the cell's widths — 19.8 of blocks in and out, double
#: buffered, and 4.4 of scratch.
VMEM_LIMIT = 96 * 1024 * 1024


class Plan(NamedTuple):
    """The static side of a call: the heads, the taps, the tile, the norm."""
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    taps: int
    tile: int
    eps: float

    @property
    def rep(self):
        return self.value_heads // self.key_heads

    @property
    def keys(self):
        return self.key_heads * self.key_dim

    @property
    def values(self):
        return self.value_heads * self.value_dim

    @property
    def mixed(self):
        """Lanes the convolution runs over: q, k and v side by side."""
        return 2 * self.keys + self.values

    def blocks(self):
        """[(section 0 q / 1 k / 2 v, the first head, how many heads, the first
        lane of the projection, a head's lanes)]: the lane blocks a group of
        positions is walked in, ``BLOCK`` lanes of whole heads each (one head
        where it is wider)."""
        out = []
        for section, heads, width in ((0, self.key_heads, self.key_dim),
                                      (1, self.key_heads, self.key_dim),
                                      (2, self.value_heads, self.value_dim)):
            first_lane = section * self.keys
            for head in range(0, heads, max(1, BLOCK // width)):
                count = min(max(1, BLOCK // width), heads - head)
                out.append((section, head, count, first_lane + head * width, width))
        return out


def _interpret():
    return not hw.on_tpu()


def _sigmoid(x):
    """``1 / (1 + exp(-x))``: the reciprocal is the unit's estimate and two
    Newton steps (float32 to the last bit from an estimate of eight bits; a
    full division is ten more vector operations an element, and these kernels
    are bound by those).  The exponent is held under ``EXP_MAX``: past it
    ``exp`` overflows and a Newton step makes ``inf * 0`` of it, where the
    answer is 0 to 35 places."""
    grown = 1.0 + jnp.exp(jnp.minimum(-x, EXP_MAX))
    estimate = pl.reciprocal(grown, approx=True)
    estimate = estimate * (2.0 - grown * estimate)
    return estimate * (2.0 - grown * estimate)


def _window(first, second, offset):
    """``ROWS`` positions out of two groups of ``ROWS`` that follow one another,
    from ``offset`` (inside the first: 0 < offset < ``ROWS``) on: position t
    holds ``[first ; second][t + offset]``.  One select and one sublane
    rotation."""
    row = jax.lax.broadcasted_iota(jnp.int32, first.shape, 0)
    return pltpu.roll(jnp.where(row < offset, second, first), ROWS - offset, axis=0)


def _convolved(previous, current, taps_ref, lanes, plan):
    """(the convolution's output at ``current``'s positions, the shifted inputs
    a tap): ``sum_j taps[j] * x[t - (K - 1) + j]``, summed in the XLA form's
    order."""
    shifted = [_window(previous, current, ROWS - (plan.taps - 1) + j)
               for j in range(plan.taps - 1)] + [current]
    out = shifted[0] * taps_ref[0:1, lanes]
    for j in range(1, plan.taps):
        out = out + shifted[j] * taps_ref[j:j + 1, lanes]
    return out, shifted


def _head_sums(values, count, width):
    """The sum over each head's ``width`` lanes of (ROWS, count * width)
    ``values``, handed back to the head's lanes."""
    sums = [jnp.broadcast_to(jnp.sum(values[:, h * width:(h + 1) * width], axis=-1,
                                     keepdims=True), (values.shape[0], width))
            for h in range(count)]
    return sums[0] if count == 1 else jnp.concatenate(sums, axis=-1)


def _before(i, first, opens, x_ref, before_ref, lanes):
    """The ``ROWS`` positions before the tile's group ``i`` (which begins at
    ``first``): the group before it, or the halo — zeros where the tile
    ``opens`` the sequence."""
    inside = pl.ds(pl.multiple_of(jnp.maximum(first - ROWS, 0), ROWS), ROWS)
    return jnp.where(i == 0, jnp.where(opens, 0.0, before_ref[:, lanes]), x_ref[inside, lanes])


def _fwd_kernel(x_ref, before_ref, taps_ref, q_ref, k_ref, v_ref, *, plan):
    scale, opens = plan.key_dim ** -0.5, pl.program_id(1) == 0

    def group(i, carry):
        first = pl.multiple_of(i * ROWS, ROWS)
        rows = pl.ds(first, ROWS)
        for section, head, count, lane, width in plan.blocks():
            lanes = slice(lane, lane + count * width)
            conv, _ = _convolved(_before(i, first, opens, x_ref, before_ref, lanes),
                                 x_ref[rows, lanes], taps_ref, lanes, plan)
            out = conv * _sigmoid(conv)
            if section == 2:
                v_ref[rows, head * width:(head + count) * width] = out
                continue
            out = out * jax.lax.rsqrt(_head_sums(out * out, count, width) + plan.eps)
            if section == 0:
                out = out * scale
            for h in range(count):   # a key head's lanes, once a value head it serves
                for r in range(plan.rep):
                    at = ((head + h) * plan.rep + r) * width
                    (q_ref, k_ref)[section][rows, at:at + width] = out[:, h * width:(h + 1) * width]
        return carry

    jax.lax.fori_loop(0, plan.tile // ROWS, group, None)


def _bwd_kernel(x_ref, before_ref, after_ref, taps_ref, dq_ref, dq_after, dk_ref, dk_after,
                dv_ref, dv_after, dz_ref, dx_ref, dtaps_ref, dconv, sums, *, plan):
    """Scratch: the cotangent of the convolution's output over the tile and the
    ``ROWS`` positions after it (tile + ROWS, lanes); the taps' gradient,
    ``ROWS`` partial sums a tap (taps * ROWS, lanes), carried over a batch
    entry's tiles."""
    t, last = pl.program_id(1), pl.num_programs(1) - 1
    tile, taps, rep = plan.tile, plan.taps, plan.rep
    groups, scale = tile // ROWS, plan.key_dim ** -0.5
    cotangents = ((dq_ref, dq_after), (dk_ref, dk_after), (dv_ref, dv_after))

    @pl.when(t == 0)
    def _():
        sums[...] = jnp.zeros(sums.shape, jnp.float32)

    def through(i, carry):
        """Group ``i`` of the tile, or (``i == groups``) the ``ROWS`` positions
        after it, which the tile's own cotangent reads: the convolution's
        output and SiLU again, the cotangent of the convolution's output into
        ``dconv`` (zeros after the sequence), and — the tile's own positions
        alone — their part of the taps' gradient."""
        first = pl.multiple_of(i * ROWS, ROWS)
        own = i < groups
        # a group of the tile, clamped where the halo's blocks are read instead
        rows = pl.ds(pl.multiple_of(jnp.minimum(first, tile - ROWS), ROWS), ROWS)
        counted = jnp.where(own | (t < last), 1.0, 0.0)
        for section, head, count, lane, width in plan.blocks():
            lanes = slice(lane, lane + count * width)
            current = jnp.where(own, x_ref[rows, lanes], after_ref[:, lanes])
            conv, shifted = _convolved(_before(i, first, t == 0, x_ref, before_ref, lanes),
                                       current, taps_ref, lanes, plan)
            gate = _sigmoid(conv)
            out = conv * gate
            ref, behind = cotangents[section]
            given = lambda at, width: jnp.where(own, ref[rows, at:at + width],
                                                behind[:, at:at + width])
            if section == 2:
                dout = given(head * width, count * width)
            else:   # a key head's value heads, summed as they are loaded
                handed = []
                for h in range(count):
                    at = (head + h) * rep * width
                    total = given(at, width)
                    for r in range(1, rep):
                        total = total + given(at + r * width, width)
                    handed.append(total)
                handed = handed[0] if count == 1 else jnp.concatenate(handed, axis=-1)
                # out * rsqrt(sum(out^2) + eps): the norm's own cotangent rule
                inverse = jax.lax.rsqrt(_head_sums(out * out, count, width) + plan.eps)
                along = _head_sums(handed * out, count, width)
                dout = inverse * (handed - out * (inverse * inverse * along))
                if section == 0:
                    dout = dout * scale
            dpre = dout * (gate * (1.0 + conv * (1.0 - gate))) * counted
            dconv[pl.ds(first, ROWS), lanes] = dpre
            mine = jnp.where(own, dpre, 0.0)
            for j in range(taps):
                part = slice(j * ROWS, (j + 1) * ROWS)
                sums[part, lanes] = sums[part, lanes] + mine * shifted[j]
        return carry

    jax.lax.fori_loop(0, groups + 1, through, None)

    def back(i, carry):
        first = pl.multiple_of(i * ROWS, ROWS)
        rows = pl.ds(first, ROWS)
        for _, _, count, lane, width in plan.blocks():
            lanes = slice(lane, lane + count * width)
            current = dconv[rows, lanes]
            following = dconv[pl.ds(pl.multiple_of(first + ROWS, ROWS), ROWS), lanes]
            # dx[u] = sum_j taps[j] * dconv[u + (K - 1) - j]
            later = [_window(current, following, taps - 1 - j) for j in range(taps - 1)] + [current]
            dx = later[0] * taps_ref[0:1, lanes]
            for j in range(1, taps):
                dx = dx + later[j] * taps_ref[j:j + 1, lanes]
            dx_ref[rows, lanes] = dx
        return carry

    jax.lax.fori_loop(0, groups, back, None)
    dx_ref[:, plan.mixed:] = dz_ref[...]

    @pl.when(t == last)
    def _():
        for j in range(taps):
            dtaps_ref[j:j + 1, :] = jnp.sum(sums[j * ROWS:(j + 1) * ROWS, :], axis=0,
                                            keepdims=True)


def _call(kernel, name, plan, out_shape, in_specs, out_specs, scratch, grid, order):
    return pl.pallas_call(
        functools.partial(kernel, plan=plan), out_shape=out_shape, grid=grid, in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=scratch, name=name, interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", order),
                                             vmem_limit_bytes=VMEM_LIMIT))


def _specs(length, plan):
    """Block specs over the grid (batch, tile): ``rows(width)`` a tile's rows of
    the first ``width`` lanes of a (B, L, >= width) array, ``before(width)`` and
    ``after(width)`` the ``ROWS`` positions on either side of it (the first
    tile's ``before`` and the last tile's ``after`` are blocks of the sequence
    itself, which the kernels do not read), and the taps whole."""
    groups, all_groups = plan.tile // ROWS, length // ROWS
    rows = lambda width: pl.BlockSpec((None, plan.tile, width), lambda b, t: (b, t, 0))
    before = lambda width: pl.BlockSpec(
        (None, ROWS, width), lambda b, t: (b, jnp.maximum(t * groups - 1, 0), 0))
    after = lambda width: pl.BlockSpec(
        (None, ROWS, width), lambda b, t: (b, jnp.minimum((t + 1) * groups, all_groups - 1), 0))
    return rows, before, after, pl.BlockSpec((plan.taps, plan.mixed), lambda b, t: (0, 0))


def _forward(projected, taps, plan):
    """q and k (B, L, value heads * key dim) and v (B, L, values)."""
    b, length, _ = projected.shape
    rows, before, _, whole = _specs(length, plan)
    wide = plan.value_heads * plan.key_dim
    result = lambda width: jax.ShapeDtypeStruct((b, length, width), jnp.float32)
    return _call(
        _fwd_kernel, "gdn_operands_fwd", plan, (result(wide), result(wide), result(plan.values)),
        [rows(plan.mixed), before(plan.mixed), whole], (rows(wide), rows(wide), rows(plan.values)),
        [], (b, length // plan.tile), "parallel")(projected, projected, taps.T)


def _backward(plan, kept, cotangents):
    projected, taps = kept
    dq, dk, dv, dz = cotangents
    b, length, lanes = projected.shape
    rows, before, after, whole = _specs(length, plan)
    wide = plan.value_heads * plan.key_dim
    room = lambda positions: pltpu.VMEM((positions, plan.mixed), jnp.float32)
    dprojected, dtaps = _call(
        _bwd_kernel, "gdn_operands_bwd", plan,
        (jax.ShapeDtypeStruct(projected.shape, jnp.float32),
         jax.ShapeDtypeStruct((b, plan.taps, plan.mixed), jnp.float32)),
        [rows(plan.mixed), before(plan.mixed), after(plan.mixed), whole, rows(wide), after(wide),
         rows(wide), after(wide), rows(plan.values), after(plan.values), rows(lanes - plan.mixed)],
        (rows(lanes), pl.BlockSpec((None, plan.taps, plan.mixed), lambda b, t: (b, 0, 0))),
        [room(plan.tile + ROWS), room(plan.taps * ROWS)],
        (b, length // plan.tile), "arbitrary")(
            projected, projected, projected, taps.T, dq, dq, dk, dk, dv, dv, dz)
    return dprojected, jnp.sum(dtaps, axis=0).T


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _fused(projected, taps, plan):
    """(q, k, v, z): the kernel's three and the projection's last lanes."""
    return _forward(projected, taps, plan) + (projected[..., plan.mixed:],)


def _fused_fwd(projected, taps, plan):
    return _fused.fun(projected, taps, plan), (projected, taps)


_fused.defvjp(_fused_fwd, _backward)


def fused_operands(projected, taps, key_heads, value_heads, key_dim, value_dim, eps, tile):
    """The projection's output (B, L, 2 keys + 2 values) float32 — q, k, v and z
    side by side — and the taps (2 keys + values, K) -> q and k (B, L, value
    heads * key dim), L2-normalised a head and q scaled by ``key_dim ** -0.5``,
    each key head's lanes ``value_heads // key_heads`` times over, v and z (B,
    L, values), z the projection's last lanes untouched; with its own backward
    pass.  ``L`` divides into tiles of ``tile`` positions, whole sublane tiles."""
    plan = Plan(key_heads, value_heads, key_dim, value_dim, taps.shape[-1], tile, eps)
    length, lanes = projected.shape[1:]
    if tile % ROWS or length % tile or plan.taps > ROWS or lanes != plan.mixed + plan.values \
            or taps.shape[0] != plan.mixed or value_heads % key_heads:
        raise ValueError(
            "the operands kernel takes a length of whole tiles of whole sublane tiles, at most %d "
            "taps and a projection of 2 keys + 2 values lanes, not length %d in tiles of %d, taps "
            "%s and %d lanes for heads of %d x %d and %d x %d" % (
                ROWS, length, tile, tuple(taps.shape), lanes, key_heads, key_dim, value_heads,
                value_dim))
    return _fused(projected.astype(jnp.float32), taps.astype(jnp.float32), plan)


# --------------------------------------------------------------------------- #
#  The chooser                                                                #
# --------------------------------------------------------------------------- #

#: The form ``forced_form`` holds ``operands_form`` to; ``None`` outside it.
_forced = None


@contextlib.contextmanager
def forced_form(form):
    """Hold ``operands_form`` to ``"kernel"`` or ``"xla"`` for what is TRACED
    inside the block.  The seam of the parity tests and of
    scripts/pallas_tpu_check.py's XLA column; no training path enters it."""
    global _forced
    if form not in ("kernel", "xla"):
        raise ValueError("forced_form takes 'kernel' or 'xla', got %r" % (form,))
    previous, _forced = _forced, form
    try:
        yield
    finally:
        _forced = previous


def tile_for(length):
    """Positions a tile for a sequence of ``length``: ``TILE`` where tiles of it
    divide the length, the whole of a shorter sequence of whole sublane tiles,
    ``None`` where neither (a ragged length: the XLA form's)."""
    if length % ROWS or (length > TILE and length % TILE):
        return None
    return min(length, TILE)


def operands_form(length, key_dim, value_dim, taps, dtype):
    """``"kernel"`` or ``"xla"`` for a projection of ``dtype`` over sequences of
    ``length`` under key heads of ``key_dim`` and value heads of ``value_dim``
    lanes and ``taps`` taps: the kernel on a TPU (``utils.hw.on_tpu``) where it
    takes the shape — float32 (a narrower run keeps the XLA form's roundings),
    heads of whole lanes, taps within the halo, a length of whole tiles
    (``tile_for``) — and the caller's XLA form everywhere else.  Inside
    ``forced_form`` the forced form answers, for heads of any width."""
    takes = (jnp.dtype(dtype) == jnp.float32 and taps <= ROWS and tile_for(length) is not None)
    if _forced is not None:
        if _forced == "kernel" and not takes:
            raise ValueError("the operands kernel takes a float32 projection, at most %d taps and "
                             "a length of whole tiles, not %s, %d taps and length %d"
                             % (ROWS, jnp.dtype(dtype).name, taps, length))
        return _forced
    whole_lanes = key_dim % LANE == 0 and value_dim % LANE == 0
    return "kernel" if hw.on_tpu() and takes and whole_lanes else "xla"


@functools.lru_cache(maxsize=None)
def _announce(form, shape, heads, taps):
    tiles = "; tiles of %d positions" % tile_for(shape[1]) if form == "kernel" else ""
    info("operands form for a projection %s, heads %s, %d taps: %s%s" % (
        "x".join(map(str, shape)), heads, taps, form, tiles))


def gdn_operands(projected, taps, key_heads, value_heads, key_dim, value_dim, eps, xla_form):
    """q, k (B, L, value heads, key dim), v and z (B, L, value heads, value
    dim) out of the projection's output, the heads' L2 norm under ``eps``:
    ``fused_operands`` where ``operands_form`` says so, else
    ``xla_form(projected, taps)`` (models/qwen3_next.py's ``split_heads``).  On
    a TPU each decision is logged once a shape."""
    b, length, _ = projected.shape
    form = operands_form(length, key_dim, value_dim, taps.shape[-1], projected.dtype)
    if hw.on_tpu():
        _announce(form, tuple(projected.shape),
                  "%dx%d / %dx%d" % (key_heads, key_dim, value_heads, value_dim), taps.shape[-1])
    if form != "kernel":
        return xla_form(projected, taps)
    q, k, v, z = fused_operands(projected, taps, key_heads, value_heads, key_dim, value_dim, eps,
                                tile_for(length))
    by_head = lambda a, width: a.reshape(b, length, value_heads, width)
    return by_head(q, key_dim), by_head(k, key_dim), by_head(v, value_dim), by_head(z, value_dim)
