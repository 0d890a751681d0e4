"""The chunked gated delta rule as one Pallas kernel pair, forward and backward.

What models/qwen3_next.py's ``chunked_delta_rule`` does in XLA — every chunk's
products made at once as (workers, heads, chunks)-shaped tensors through HBM,
the heads' axis moved behind the chunks and back, the triangular system
inverted in six rounds of batched products, then a ``lax.scan`` of three small
products a chunk — done here from VMEM, the equations and their names those of
that function's docstring:

- **Forward** (``delta_rule_fwd``): one grid step a (batch, head, tile of
  ``TILE_CHUNKS`` chunks), the tiles of a head one after another.  q, k, v and o
  are (tile of positions, the head's lanes) blocks of the (B, L, H * D) arrays
  AS THEY LIE; g and beta arrive head-major (a 1.5 MB transpose in XLA), a row
  of ``BLOCK_CHUNKS`` chunks' positions along the lanes.  The head's state, (Dk,
  Dv) float32, is a VMEM scratch zeroed at the head's first tile and carried
  over its tiles.  A tile is straight-line code, ``BLOCK_CHUNKS`` = 2 chunks
  side by side a block and the blocks stage by stage (their chains of
  dependent products fill each other's waits for the MXU), so
  that at a chunk of 64 every operand of a product has 128 rows: the two
  chunks' system is ONE block-diagonal (128, 128) matrix (the masks keep a
  chunk to itself), inverted in the XLA form's own rounds, ``T <- T - T (a
  between the halves of a block) T``; U and W are one product ``T [beta v |
  beta e^G k]``; then, chunk after chunk, ``[W ; q e^G] S`` is one product,
  ``V' = U - W S``, ``S <- e^{G_last} S + (k e^{G_last - G})^T V'``; the two
  chunks' outputs are ``q e^G S + tril(Q K^T * D) V'`` in one product more.
  Nothing chunk-shaped reaches HBM.
- **Backward** (``delta_rule_bwd``): the ``custom_vjp``'s forward rule runs the
  same kernel with one more output, the state ENTERING each chunk (64 KiB a
  chunk a head), and the backward kernel walks a head's tiles in REVERSE with
  the state's cotangent in a second VMEM scratch.  A tile: the forward's
  chunk-local matrices made again (nothing of them was kept), ``V'`` from the
  kept states; then, chunk after chunk from the last, ``dV' = (k e^{G_last -
  G}) dS + P^T do`` and ``dS <- e^{G_last} dS + [W ; q e^G]^T [-dV' ; do]``;
  then, chunk pairs side by side again, everything that hangs off dV': the
  inverse's cotangent is ``dA = -tril(dvb U^T + dkbg W^T, -1)`` (no cotangent of
  T itself is formed), the decay's ``E = dA * A + dP * P`` gives dG by its row
  sums less its column sums, and dg is dG summed from each position to its
  chunk's end.  dq, dk, dv leave as blocks of (B, L, H * D), dg and dbeta as
  the small head-major arrays.

**The same arithmetic as the XLA form, and no less**: float32 operands into
every product, float32 accumulation; the products carry the process's matmul
precision as XLA's do.  The cumulative sum of g, the exponentials, the masks
and the differences ``G_i - G_j`` are float32 VECTOR work: a cumulative sum is a
masked sum along the lanes, a column is turned into a row by a masked sum under
the diagonal, never by a product (which would round g to bfloat16 at the
default precision).  Forbidden pairs are masked BEFORE the exponential.

**One chooser** (``delta_rule_form``): on a TPU, for shapes the kernel takes,
the kernel; else the caller's XLA form (ragged lengths included: no padded
copy in front of a kernel).  No flag and no environment variable;
``forced_form`` is the one scoped seam, for the tests and
scripts/pallas_tpu_check.py.  Off a TPU a forced kernel interprets.
"""

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import hw, info
from .attention import NEG
from .pallas_kernels import LANE

#: Positions a chunk of the compiled kernel: two chunks side by side are the
#: 128 rows of every product's operands (grid/configs/qwen3next-*.json's chunk).
CHUNK = 64

#: Chunks walked side by side: one block-diagonal system, one product each of
#: the chunk-local matrices.
BLOCK_CHUNKS = 2

#: Chunks a tile (a grid step): 512 positions, 256 KiB an operand.
TILE_CHUNKS = 8

#: What the compiler may use of VMEM (v5e: 128 MiB a core; its own default is
#: 16): the backward kernel holds ~9 MB — thirteen blocks in and out, double
#: buffered, and thirteen tile-sized scratches.
VMEM_LIMIT = 64 * 1024 * 1024


def _interpret():
    return not hw.on_tpu()


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """``a @ b.T``."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    """``a.T @ b``."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _across(x, width):
    """A (rows, 1) column along ``width`` lanes."""
    return jnp.broadcast_to(x, (x.shape[0], width))


def _masks(rows, chunk):
    """Boolean (rows, rows) masks over (position i of the rows, position j of
    the lanes) of ``rows`` positions holding whole chunks (``chunk`` a power of
    two, so two positions share a chunk iff their indices differ below it):
    ``low`` j <= i in i's chunk, ``strict`` j < i there, ``eye``, ``ends`` j the
    last position of i's chunk; and ``apart``, ``i ^ j``."""
    i = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
    apart = jnp.bitwise_xor(i, j)
    same = apart < chunk
    return {"low": same & (j <= i), "strict": same & (j < i), "eye": i == j, "apart": apart,
            "ends": same & (jnp.bitwise_and(j, chunk - 1) == chunk - 1)}


def _blocks(operands, chunk):
    """The chunk-local matrices of a tile, ``BLOCK_CHUNKS`` chunks side by side a
    block: ``operands`` a list, a block each, of (q, k (rows, Dk), v (rows, Dv),
    g and beta (1, rows) along the lanes) -> a list of dicts, the names
    ``chunked_delta_rule``'s; every (rows, rows) matrix is block-diagonal, a
    chunk to itself.  Written STAGE BY STAGE over all the blocks: a block's
    inverse is a chain of twelve dependent products, each waiting ~200 cycles
    for the MXU's result, and the blocks' chains are independent — side by
    side in program order they fill each other's waits (the compiler keeps
    program order where nothing tells it otherwise)."""
    rows, dk = operands[0][1].shape
    dv = operands[0][2].shape[1]
    m = _masks(rows, chunk)
    column = lambda row: jnp.sum(jnp.where(m["eye"], row, 0.0), axis=1, keepdims=True)
    blocks = []
    for q, k, v, g_row, beta_row in operands:
        # G, the cumulative sum of g inside a chunk, as a column; the SAME numbers as a row
        total = jnp.sum(jnp.where(m["low"], g_row, 0.0), axis=1, keepdims=True)
        total_row = jnp.sum(jnp.where(m["eye"], total, 0.0), axis=0, keepdims=True)
        last = jnp.sum(jnp.where(m["ends"], total_row, 0.0), axis=1, keepdims=True)
        beta = column(beta_row)
        # masked BEFORE the exponential: above the diagonal the difference is positive and large
        decay = jnp.exp(jnp.where(m["low"], total - total_row, NEG))
        grown, left = jnp.exp(total), jnp.exp(last - total)
        blocks.append({"beta": beta, "decay": decay, "grown": grown, "left": left,
                       "leaves": jnp.exp(_across(last, dv)), "k_beta": k * _across(beta, dk),
                       "q_decayed": q * _across(grown, dk), "k_left": k * _across(left, dk)})
    for blk, (q, k, _, _, _) in zip(blocks, operands):
        both = _dot_nt(jnp.concatenate([blk["k_beta"], q], axis=0), k)
        blk["system"] = jnp.where(m["strict"], both[:rows] * blk["decay"], 0.0)
        blk["within"] = both[rows:] * blk["decay"]
        blk["solved"] = jnp.where(m["eye"], 1.0, 0.0)
    size = 1
    while size < chunk:   # unit_lower_inverse's rounds, the blocks of every chunk at once
        halves = (m["apart"] >= size) & (m["apart"] < 2 * size)
        steps = [_dot(blk["solved"], jnp.where(halves, blk["system"], 0.0)) for blk in blocks]
        for blk, step in zip(blocks, steps):
            blk["solved"] = blk["solved"] - _dot(step, blk["solved"])
        size *= 2
    for blk, (_, _, v, _, _) in zip(blocks, operands):
        solutions = _dot(blk["solved"], jnp.concatenate(
            [v * _across(blk["beta"], dv), blk["k_beta"] * _across(blk["grown"], dk)], axis=1))
        blk["writes"], blk["predicts"] = solutions[:, :dv], solutions[:, dv:]
    return blocks


def _tile_operands(q_ref, k_ref, v_ref, g_ref, beta_ref, rows):
    """``_blocks``' operands out of a tile's refs."""
    at = lambda b: slice(b * rows, (b + 1) * rows)
    return [(q_ref[at(b), :], k_ref[at(b), :], v_ref[at(b), :], g_ref[b:b + 1, :],
             beta_ref[b:b + 1, :]) for b in range(g_ref.shape[0])]


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, last_ref, *rest, chunk, tile_chunks,
                keep):
    """``rest``: with ``keep`` the output of the states entering each chunk,
    then the scratch — the carried state (Dk, Dv); a block's V' and its ``q e^G
    S`` (rows, Dv)."""
    states_ref = rest[0] if keep else None
    state, new, read = rest[-3:]
    rows = BLOCK_CHUNKS * chunk

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros(state.shape, jnp.float32)

    blocks = _blocks(_tile_operands(q_ref, k_ref, v_ref, g_ref, beta_ref, rows), chunk)
    for b, blk in enumerate(blocks):
        at = slice(b * rows, (b + 1) * rows)
        for c in range(BLOCK_CHUNKS):
            piece = slice(c * chunk, (c + 1) * chunk)
            if keep:
                states_ref[b * BLOCK_CHUNKS + c] = state[...]
            against = _dot(jnp.concatenate([blk["predicts"][piece], blk["q_decayed"][piece]],
                                           axis=0), state[...])   # [W S ; q e^G S]
            new[piece] = blk["writes"][piece] - against[:chunk]
            read[piece] = against[chunk:]
            state[...] = (state[...] * blk["leaves"][c * chunk:c * chunk + 1]
                          + _dot_tn(blk["k_left"][piece], new[piece]))
        o_ref[at, :] = read[...] + _dot(blk["within"], new[...])
    last_ref[...] = state[...]


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, do_ref, states_ref, dlast_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                dstate, stacked, against, new, dnew, dleft, dleaves, solved, decay, system, within,
                writes, predicts, k_left, *, chunk, tile_chunks):
    """Scratch: the carried cotangent of the state (Dk, Dv); then a TILE's
    worth, a chunk's rows where the forward kernel lays them: ``[W ; q e^G]``
    and ``[-dV' ; do]`` chunk under chunk, V', dV', the cotangent of ``k
    e^{G_last - G}``, a row a chunk of ``sum(dS * e^{G_last} S)`` over the
    rows, and the chunk-local matrices of the forward made again."""
    rows = BLOCK_CHUNKS * chunk
    dk_width, dv_width = k_ref.shape[1], v_ref.shape[1]
    blocks = tile_chunks // BLOCK_CHUNKS

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = dlast_ref[...]

    small = []   # a block's columns, kept as values between the passes: a vreg or two each
    # the forward's chunk-local matrices again, and V' from the kept states
    for b, blk in enumerate(_blocks(_tile_operands(q_ref, k_ref, v_ref, g_ref, beta_ref, rows),
                                    chunk)):
        at = slice(b * rows, (b + 1) * rows)
        for name, room in (("solved", solved), ("decay", decay), ("system", system),
                           ("within", within), ("writes", writes), ("predicts", predicts),
                           ("k_left", k_left)):
            room[at] = blk[name]
        small.append({name: blk[name] for name in ("beta", "grown", "left", "leaves")})
        for c in range(BLOCK_CHUNKS):
            piece = slice(c * chunk, (c + 1) * chunk)
            ci = b * BLOCK_CHUNKS + c
            stacked[ci * rows:ci * rows + chunk] = blk["predicts"][piece]
            stacked[ci * rows + chunk:(ci + 1) * rows] = blk["q_decayed"][piece]
            against[ci * rows + chunk:(ci + 1) * rows] = do_ref[ci * chunk:(ci + 1) * chunk, :]
            new[ci * chunk:(ci + 1) * chunk] = blk["writes"][piece] - _dot(
                blk["predicts"][piece], states_ref[ci])
        # P^T do, the part of dV' that no state enters, in dV's room until the chain adds its own
        dnew[at] = _dot_tn(blk["within"], do_ref[at, :])

    for ci in reversed(range(tile_chunks)):   # the chain: the state's cotangent, chunk by chunk
        b, c = divmod(ci, BLOCK_CHUNKS)
        piece = slice(ci * chunk, (ci + 1) * chunk)
        leaves = small[b]["leaves"][c * chunk:c * chunk + 1]
        carried = dstate[...]
        dnew[piece] = dnew[piece] + _dot(k_left[piece], carried)
        against[ci * rows:ci * rows + chunk] = -dnew[piece]
        dstate[...] = carried * leaves + _dot_tn(stacked[ci * rows:(ci + 1) * rows],
                                                 against[ci * rows:(ci + 1) * rows])
        dleft[piece] = _dot_nt(new[piece], carried)
        dleaves[ci:ci + 1] = jnp.sum(carried * states_ref[ci] * leaves, axis=0, keepdims=True)

    # What hangs off dV', the chunks of a block side by side again, and STAGE BY STAGE over the
    # blocks as in ``_blocks``: five products deep a block, the blocks filling each other's waits.
    m = _masks(rows, chunk)
    ats = [slice(b * rows, (b + 1) * rows) for b in range(blocks)]
    piece = lambda ci: slice(ci * chunk, (ci + 1) * chunk)
    # a row a chunk of the block, true at the chunk's last position
    ending = (jax.lax.broadcasted_iota(jnp.int32, (BLOCK_CHUNKS, rows), 1)
              == jax.lax.broadcasted_iota(jnp.int32, (BLOCK_CHUNKS, rows), 0) * chunk + chunk - 1)
    work = []
    for b, at in enumerate(ats):
        # [do ; dV'] S^T a chunk: the cotangents of q e^G and, negated, of W
        backs = [_dot_nt(jnp.concatenate([do_ref[piece(ci), :], dnew[piece(ci)]], axis=0),
                         states_ref[ci]) for ci in range(b * BLOCK_CHUNKS, (b + 1) * BLOCK_CHUNKS)]
        work.append({"dq_decayed": jnp.concatenate([back[:chunk] for back in backs], axis=0),
                     "dpredicts": -jnp.concatenate([back[chunk:] for back in backs], axis=0),
                     "dwithin": _dot_nt(do_ref[at, :], new[at])})
    for w, at in zip(work, ats):
        # through U = T (beta v) and W = T (beta e^G k): T^T [dU | dW] ...
        w["through"] = _dot_tn(solved[at], jnp.concatenate([dnew[at], w["dpredicts"]], axis=1))
    for w, at in zip(work, ats):
        # ... and T's own cotangent folded into A's: dA = -tril(dvb U^T + dkbg W^T, -1)
        w["dsystem"] = -jnp.where(m["strict"], _dot_nt(
            w["through"], jnp.concatenate([writes[at], predicts[at]], axis=1)), 0.0)
        w["dboth"] = jnp.concatenate([w["dsystem"] * decay[at], w["dwithin"] * decay[at]], axis=0)
    for b, (w, at) in enumerate(zip(work, ats)):
        q, k = q_ref[at, :], k_ref[at, :]
        beta, grown, left = (small[b][name] for name in ("beta", "grown", "left"))
        k_beta = k * _across(beta, dk_width)
        onto = _dot(w["dboth"], k)
        dk_grown = w["through"][:, dv_width:]
        dk_beta = onto[:rows] + dk_grown * _across(grown, dk_width)
        dq_ref[at, :] = onto[rows:] + w["dq_decayed"] * _across(grown, dk_width)
        dk_ref[at, :] = (_dot_tn(w["dboth"], jnp.concatenate([k_beta, q], axis=0))
                         + dleft[at] * _across(left, dk_width) + dk_beta * _across(beta, dk_width))
        w["dbeta"] = jnp.sum(dk_beta * k, axis=1, keepdims=True)
        w["dgrown"] = (jnp.sum(w["dq_decayed"] * q, axis=1, keepdims=True)
                       + jnp.sum(dk_grown * k_beta, axis=1, keepdims=True))
    for b, (w, at) in enumerate(zip(work, ats)):
        beta, grown = small[b]["beta"], small[b]["grown"]
        dwrites = w["through"][:, :dv_width]
        dv_ref[at, :] = dwrites * _across(beta, dv_width)
        dbeta = w["dbeta"] + jnp.sum(dwrites * v_ref[at, :], axis=1, keepdims=True)
        # the decay: E = dD * D; dG_i gets E's row sum, dG_j loses its column sum
        through_decay = w["dsystem"] * system[at] + w["dwithin"] * within[at]
        dleft_left = jnp.sum(dleft[at] * k_left[at], axis=1, keepdims=True)
        dtotal = (jnp.sum(through_decay, axis=1, keepdims=True) + grown * w["dgrown"] - dleft_left)
        # a chunk's last position: e^{G_last - G} and e^{G_last} both hang on it
        carried_sums = jnp.sum(dleaves[b * BLOCK_CHUNKS:(b + 1) * BLOCK_CHUNKS], axis=1,
                               keepdims=True)
        at_ends = (jnp.sum(jnp.where(ending, _across(carried_sums, rows), 0.0), axis=0,
                           keepdims=True)
                   + jnp.sum(jnp.where(m["ends"], dleft_left, 0.0), axis=0, keepdims=True))
        by_row = at_ends - jnp.sum(through_decay, axis=0, keepdims=True)
        dtotal = dtotal + jnp.sum(jnp.where(m["eye"], by_row, 0.0), axis=1, keepdims=True)
        # G is a cumulative sum: dg_j is dG summed from j to its chunk's end
        dg_ref[b:b + 1, :] = jnp.sum(jnp.where(m["low"], dtotal, 0.0), axis=0, keepdims=True)
        dbeta_ref[b:b + 1, :] = jnp.sum(jnp.where(m["eye"], dbeta, 0.0), axis=0, keepdims=True)


def _head_major(a, plan):
    """g or beta (B, L, H) -> (B, H, tiles, blocks a tile, positions a block):
    a block's positions along the lanes."""
    chunk, tile_chunks = plan
    b, length, heads = a.shape
    rows = BLOCK_CHUNKS * chunk
    return a.transpose(0, 2, 1).reshape(b, heads, length // (tile_chunks * chunk),
                                        tile_chunks // BLOCK_CHUNKS, rows)


def _specs(shape, dv, plan, reverse=False):
    """(the grid; block specs of a (B, L, H * Dk) array, of a (B, L, H * Dv)
    one, of g's kind head-major, of the (B, H, Dk, Dv) state's kind, of the
    states a chunk (B, H, chunks, Dk, Dv)), the tiles walked last to first
    under ``reverse``."""
    chunk, tile_chunks = plan
    b, length, heads, dk = shape
    tile, rows = tile_chunks * chunk, BLOCK_CHUNKS * chunk
    tiles = length // tile
    turn = (lambda t: tiles - 1 - t) if reverse else (lambda t: t)
    wide = lambda width: pl.BlockSpec((None, tile, width), lambda b, h, t: (b, turn(t), h))
    return ((b, heads, tiles), wide(dk), wide(dv),
            pl.BlockSpec((None, None, None, tile // rows, rows),
                         lambda b, h, t: (b, h, turn(t), 0, 0)),
            pl.BlockSpec((None, None, dk, dv), lambda b, h, t: (b, h, 0, 0)),
            pl.BlockSpec((None, None, tile_chunks, dk, dv), lambda b, h, t: (b, h, turn(t), 0, 0)))


def _call(kernel, name, out_shape, in_specs, out_specs, scratch, grid, **static):
    return pl.pallas_call(
        functools.partial(kernel, **static), out_shape=out_shape, grid=grid, in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=scratch, name=name, interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT))


def _forward(q, k, v, g, beta, plan, keep):
    """(o (B, L, H * Dv), the last state (B, H, Dk, Dv), with ``keep`` the state
    entering each chunk (B, H, chunks, Dk, Dv))."""
    chunk, tile_chunks = plan
    b, length, heads, dk = q.shape
    dv = v.shape[-1]
    rows = BLOCK_CHUNKS * chunk
    grid, by_key, by_value, by_gate, whole, a_chunk = _specs(q.shape, dv, plan)
    room = lambda *shape: pltpu.VMEM(shape, jnp.float32)
    result = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    kept = ((result(b, heads, length // chunk, dk, dv), a_chunk),) if keep else ()
    return _call(
        _fwd_kernel, "delta_rule_fwd",
        (result(b, length, heads * dv), result(b, heads, dk, dv)) + tuple(s for s, _ in kept),
        [by_key, by_key, by_value, by_gate, by_gate],
        (by_value, whole) + tuple(spec for _, spec in kept),
        [room(dk, dv), room(rows, dv), room(rows, dv)], grid,
        chunk=chunk, tile_chunks=tile_chunks, keep=keep)(
            q.reshape(b, length, heads * dk), k.reshape(b, length, heads * dk),
            v.reshape(b, length, heads * dv), _head_major(g, plan), _head_major(beta, plan))


def _backward(plan, kept, cotangents):
    q, k, v, g, beta, states = kept
    dout, dlast = cotangents
    chunk, tile_chunks = plan
    b, length, heads, dk = q.shape
    dv = v.shape[-1]
    rows, tile = BLOCK_CHUNKS * chunk, tile_chunks * chunk
    grid, by_key, by_value, by_gate, whole, a_chunk = _specs(q.shape, dv, plan, reverse=True)
    room = lambda *shape: pltpu.VMEM(shape, jnp.float32)
    gates = _head_major(g, plan)
    dq, dk_, dv_, dg, dbeta = _call(
        _bwd_kernel, "delta_rule_bwd",
        (jax.ShapeDtypeStruct((b, length, heads * dk), jnp.float32),
         jax.ShapeDtypeStruct((b, length, heads * dk), jnp.float32),
         jax.ShapeDtypeStruct((b, length, heads * dv), jnp.float32),
         jax.ShapeDtypeStruct(gates.shape, jnp.float32),
         jax.ShapeDtypeStruct(gates.shape, jnp.float32)),
        [by_key, by_key, by_value, by_gate, by_gate, by_value, a_chunk, whole],
        (by_key, by_key, by_value, by_gate, by_gate),
        [room(dk, dv), room(tile_chunks * rows, dk), room(tile_chunks * rows, dv), room(tile, dv),
         room(tile, dv), room(tile, dk), room(tile_chunks, dv), room(tile, rows), room(tile, rows),
         room(tile, rows), room(tile, rows), room(tile, dv), room(tile, dk), room(tile, dk)],
        grid, chunk=chunk, tile_chunks=tile_chunks)(
            q.reshape(b, length, heads * dk), k.reshape(b, length, heads * dk),
            v.reshape(b, length, heads * dv), gates, _head_major(beta, plan),
            dout.reshape(b, length, heads * dv), states, dlast)
    position_major = lambda a: a.reshape(b, heads, length).transpose(0, 2, 1)
    return (dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
            position_major(dg), position_major(dbeta))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _fused(q, k, v, g, beta, plan):
    out, last = _forward(q, k, v, g, beta, plan, keep=False)
    return out.reshape(v.shape), last


def _fused_fwd(q, k, v, g, beta, plan):
    out, last, states = _forward(q, k, v, g, beta, plan, keep=True)
    return (out.reshape(v.shape), last), (q, k, v, g, beta, states)


_fused.defvjp(_fused_fwd, _backward)


def fused_delta_rule(q, k, v, g, beta, chunk, tile_chunks):
    """``chunked_delta_rule``'s contract — q and k (B, L, H, Dk), v (B, L, H,
    Dv), g and beta (B, L, H), all float32 -> (o (B, L, H, Dv), the last state
    (B, H, Dk, Dv)), S_0 = 0 — with its own backward pass, which honours a
    cotangent of the last state too.  ``L`` divides into tiles of
    ``tile_chunks`` chunks, a whole number of ``BLOCK_CHUNKS``; ``chunk`` is a
    power of two."""
    length = q.shape[1]
    if chunk & (chunk - 1) or tile_chunks % BLOCK_CHUNKS or length % (tile_chunks * chunk):
        raise ValueError("the delta rule kernel takes chunks of a power of two and a length of "
                         "whole tiles of an even count of them, not chunk %d, %d a tile, length %d"
                         % (chunk, tile_chunks, length))
    args = [a.astype(jnp.float32) for a in (q, k, v, g, beta)]
    return _fused(*args, (chunk, tile_chunks))


# --------------------------------------------------------------------------- #
#  The chooser                                                                #
# --------------------------------------------------------------------------- #

#: The form ``forced_form`` holds ``delta_rule_form`` to; ``None`` outside it.
_forced = None


@contextlib.contextmanager
def forced_form(form):
    """Hold ``delta_rule_form`` to ``"kernel"`` or ``"xla"`` for what is TRACED
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


def tile_chunks_for(length, chunk):
    """Chunks a tile for a sequence of ``length``: ``TILE_CHUNKS`` where tiles of
    them divide it, the whole of a shorter sequence of whole blocks of chunks,
    ``None`` where neither (a ragged length: the XLA form pads it)."""
    chunks, ragged = divmod(length, chunk)
    if ragged or chunks % BLOCK_CHUNKS or (chunks > TILE_CHUNKS and chunks % TILE_CHUNKS):
        return None
    return min(chunks, TILE_CHUNKS)


def delta_rule_form(length, chunk, dk, dv):
    """``"kernel"`` or ``"xla"`` for sequences of ``length`` in chunks of ``chunk``
    under heads of ``dk`` by ``dv``: the kernel on a TPU (``utils.hw.on_tpu``)
    where it takes the shape — ``chunk`` the kernel's ``CHUNK``, ``dk`` and
    ``dv`` whole lanes, ``length`` whole tiles (``tile_chunks_for``) — and the
    caller's XLA form everywhere else.  Inside ``forced_form`` the forced form
    answers, for any power-of-two chunk and any length of whole tiles."""
    tile_chunks = tile_chunks_for(length, chunk)
    if _forced is not None:
        if _forced == "kernel" and (tile_chunks is None or chunk & (chunk - 1)):
            raise ValueError("the delta rule kernel takes a length of whole tiles of chunks of a "
                             "power of two, not %d in chunks of %d" % (length, chunk))
        return _forced
    takes = chunk == CHUNK and dk % LANE == 0 and dv % LANE == 0 and tile_chunks is not None
    return "kernel" if hw.on_tpu() and takes else "xla"


@functools.lru_cache(maxsize=None)
def _announce(form, shape, chunk):
    tiles = ""
    if form == "kernel":
        tiles = "; tiles of %d chunks" % tile_chunks_for(shape[1], chunk)
    info("delta rule form for q %s, chunk %d: %s%s" % ("x".join(map(str, shape)), chunk, form, tiles))


def gated_delta_rule(q, k, v, g, beta, chunk, xla_form):
    """``fused_delta_rule`` where ``delta_rule_form`` says so, else ``xla_form(q,
    k, v, g, beta, chunk)`` (models/qwen3_next.py's ``chunked_delta_rule``).  On
    a TPU each decision is logged once a shape."""
    length, dk, dv = q.shape[1], q.shape[-1], v.shape[-1]
    form = delta_rule_form(length, chunk, dk, dv)
    if hw.on_tpu():
        _announce(form, tuple(q.shape), chunk)
    if form != "kernel":
        return xla_form(q, k, v, g, beta, chunk)
    return fused_delta_rule(q, k, v, g, beta, chunk, tile_chunks_for(length, chunk))
