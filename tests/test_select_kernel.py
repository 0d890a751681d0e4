"""ops/select.py — the threshold by counting — in interpreter mode on the CPU,
at small whole-lane shapes, against models/keye_vl2.py's sort form, bit for
bit: the same pairs from seeded scores, ties across the threshold, zeros of
both signs, infinities, NaNs, queries with no more causal keys than ``topk``,
a later chunk's positions, and under ``vmap`` as the step calls it; and the
chooser off a TPU.  (The kernel compiled for the described chip at the cell's
shape: tests/test_reshard.py, where every such program lives.)"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aggregathor_tpu.models import keye_vl2
from aggregathor_tpu.ops import select

CHUNK, WORKERS = 8, 3


@functools.lru_cache(maxsize=None)
def selection(form, length, topk, workers):
    """``top_keys`` of (workers, 1, CHUNK, length) scores under ``form``,
    jitted once a shape."""
    def pairs(scores, q_pos):
        with select.forced_form(form):
            return jax.vmap(lambda scores: keye_vl2.top_keys(scores, q_pos, topk))(scores)

    return jax.jit(pairs)


def seeded(length, place, workers=1):
    return jax.random.normal(jax.random.PRNGKey(place), (workers, 1, CHUNK, length), jnp.float32)


def one_value(length, topk):
    """Every key the same: the ``topk`` lowest positions go in."""
    return jnp.full((1, 1, CHUNK, length), 0.25, jnp.float32)


def ties_across_the_threshold(length, topk):
    """Twelve keys tie where the ``topk``-th place falls among them (``r <
    ties``: some go in, by position), over seeded scores below them and a few
    above."""
    scores = -jnp.abs(seeded(length, 1)) - 1.0
    scores = scores.at[..., 3:length:length // 12].set(2.0)
    return scores.at[..., 1:max(2, topk - 5):2].set(3.0)


def zeros_at_the_threshold(length, topk):
    """Zeros of both signs tie with each other at the threshold: ``topk - 3``
    keys are above them, the rest are negative."""
    scores = -jnp.abs(seeded(length, 2)) - 1.0
    scores = scores.at[..., 0:length:7].set(0.0).at[..., 3:length:14].set(-0.0)
    return scores.at[..., 2:2 * max(0, topk - 3) + 2:2].set(1.5)


def infinities(length, topk):
    scores = seeded(length, 3)
    return scores.at[..., 4:length:9].set(-jnp.inf).at[..., 1:length:31].set(jnp.inf)


def a_nan(length, topk):
    """One NaN score a row of every other query, after every key and never in;
    and a row where all but a few are NaN, whose threshold is the NaN itself."""
    scores = seeded(length, 4).at[..., ::2, 5].set(jnp.nan).at[..., 1, 0].set(-jnp.nan)
    return scores.at[..., 3, 2:].set(jnp.nan)


#: name -> (scores of (length, topk), the chunk's first query, workers under vmap)
KINDS = {
    "normal": (lambda length, topk: seeded(length, 0), -1, 1),
    "one-value": (one_value, -1, 1),
    "ties": (ties_across_the_threshold, -1, 1),
    "zeros": (zeros_at_the_threshold, -1, 1),
    "all-negative": (lambda length, topk: -jnp.abs(seeded(length, 5)) - 0.5, -1, 1),
    "all-positive": (lambda length, topk: jnp.abs(seeded(length, 6)) + 0.5, -1, 1),
    "infinities": (infinities, -1, 1),
    "nan": (a_nan, -1, 1),
    "nan-first-chunk": (a_nan, 0, 1),
    "every-causal-key": (lambda length, topk: seeded(length, 7), 0, 1),
    "later-chunk": (lambda length, topk: seeded(length, 8), 5, 1),
    "vmap": (lambda length, topk: seeded(length, 9, WORKERS), -1, WORKERS),
}


@pytest.mark.parametrize("length,topk", [(256, 1), (256, 5), (256, 128), (256, 255), (384, 5),
                                         (384, 128), (384, 383)])
@pytest.mark.parametrize("kind", KINDS)
def test_the_kernels_pairs_are_the_sorts(kind, length, topk):
    """The kernel's pairs equal the sort form's, bit for bit.  The chunk is
    the last of its sequence (``first`` -1), its first (0: no more than 8
    causal keys a query) or its sixth."""
    make, first, workers = KINDS[kind]
    scores = make(length, topk)
    q_pos = (length - CHUNK if first < 0 else first * CHUNK) + jnp.arange(CHUNK)
    ours = np.asarray(selection("kernel", length, topk, workers)(scores, q_pos))
    theirs = np.asarray(selection("xla", length, topk, workers)(scores, q_pos))
    assert ours.dtype == theirs.dtype == np.bool_ and ours.shape == scores.shape
    assert np.array_equal(ours, theirs), np.argwhere(ours != theirs)[:8]
    causal = np.arange(length)[None, :] <= np.asarray(q_pos)[:, None]
    assert not (ours & ~causal).any()
    if not kind.startswith("nan"):
        assert np.array_equal(ours.sum(-1), np.broadcast_to(
            np.minimum(np.asarray(q_pos) + 1, topk), ours.shape[:-1]))
    if kind == "one-value":
        assert np.array_equal(ours[0, 0], causal & (np.arange(length) < topk))
    if kind == "nan":
        assert not ours[0, 0, 3].any() or topk <= 6     # its threshold is the NaN: nothing is in
        assert not ours[0, 0, ::2, 5].any()


def test_the_kernel_writes_int8_and_says_what_it_is():
    scores, q_pos = seeded(256, 10)[0], 248 + jnp.arange(CHUNK)
    pairs = select.select_threshold(scores, q_pos, 5)
    assert pairs.dtype == jnp.int8 and set(np.unique(np.asarray(pairs))) == {0, 1}
    assert select.passes(8192) == (32, 13) and select.passes(384) == (32, 9)
    calls = [eqn for eqn in jax.make_jaxpr(lambda s: select.select_threshold(s, q_pos, 5))(
        scores).jaxpr.eqns if eqn.primitive.name == "pallas_call"]
    assert len(calls) == 1 and "select_threshold" in str(calls[0].params["name"])


def test_the_chooser_off_a_tpu_and_its_seam(monkeypatch):
    assert select.select_form(512, 8192, 2048) == "xla"            # this process has no TPU
    monkeypatch.setattr(select.hw, "on_tpu", lambda: True)
    assert select.select_form(512, 8192, 2048) == "kernel"
    assert select.tile_rows(512, 8192) == select.ROWS
    assert select.select_form(512, 8192 + 64, 2048) == "xla"       # not whole lanes
    assert select.select_form(16, 8192, 2048) == "xla"             # no whole int8 tile of queries
    assert select.select_form(512, 8192, 8192) == "xla"            # every causal key: neither form
    assert select.tile_rows(512, 2 ** 20) == 4 and select.select_form(512, 2 ** 20, 5) == "xla"
    monkeypatch.undo()
    with select.forced_form("kernel"):
        assert select.select_form(8, 12, 5) == "kernel"
        assert select.select_form(8, 12, 12) == "xla"              # topk >= L inside the seam too
        with select.forced_form("xla"):
            assert select.select_form(512, 8192, 2048) == "xla"
        assert select.select_form(8, 12, 5) == "kernel"
    assert select.select_form(8, 12, 5) == "xla"
    with pytest.raises(ValueError):
        with select.forced_form("sort"):
            pass
