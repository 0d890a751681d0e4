"""ops/delta_rule.py — the gated delta rule's kernel pair — in interpreter mode
on the CPU: outputs, last state and the five gradients against
models/qwen3_next.py's ``chunked_delta_rule`` (``jax.vjp`` of the XLA form) AND
against the plain reference's recurrence walked token by token
(grid/references/qwen3_next.py ``_recurrence``); called as the step calls it
(``vmap`` over workers); a head's state zeroed at its first tile; the last
state's cotangent honoured; the chooser and its seam; the traced entry holds no
operand narrower than float32.  Products run at ``highest`` precision, so what
separates kernel and oracle is the order of float32 sums.  (The kernels
compiled for the described chip at the cell's shape: tests/test_reshard.py,
where every such program lives.)"""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aggregathor_tpu.models import qwen3_next
from aggregathor_tpu.ops import delta_rule

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def grid_module(folder, name):
    spec = importlib.util.spec_from_file_location(
        "delta_rule_test_" + name, os.path.join(ROOT, "grid", *folder, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    sys.path.insert(0, os.path.join(ROOT, "grid"))
    try:
        spec.loader.exec_module(module)
    finally:
        sys.path.remove(os.path.join(ROOT, "grid"))
    return module


reference = grid_module(("references",), "qwen3_next")


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def seeded(lead, length, heads, dk, dv, seed=3):
    """q, k, v, g, beta as ``delta_heads`` hands them over — q and k
    L2-normalised, q scaled, g negative, beta in (0, 1) — and the weights of a
    seeded scalar of the output and of the last state."""
    key = jax.random.PRNGKey(seed)
    normal = lambda place, *dims: jax.random.normal(jax.random.fold_in(key, place), lead + dims)
    return (qwen3_next.l2_normalised(normal(0, length, heads, dk)) * dk ** -0.5,
            qwen3_next.l2_normalised(normal(1, length, heads, dk)), normal(2, length, heads, dv),
            -jnp.exp(normal(3, length, heads) - 1.5), jax.nn.sigmoid(normal(4, length, heads)),
            normal(5, length, heads, dv), normal(6, heads, dk, dv))


def scalar_and_gradients(rule, workers):
    """(sum(o * w) + sum(last state * w'), (o, the last state)) and its five
    gradients, ``rule`` under ``vmap`` where the inputs carry a workers' axis."""
    def scalar(q, k, v, g, beta, w_out, w_state):
        out, state = (jax.vmap(rule) if workers else rule)(q, k, v, g, beta)
        return jnp.sum(out * w_out) + jnp.sum(state * w_state), (out, state)

    return jax.jit(jax.value_and_grad(scalar, argnums=(0, 1, 2, 3, 4), has_aux=True))


def gap(ours, theirs):
    return float(jnp.max(jnp.abs(ours - theirs)) / jnp.max(jnp.abs(theirs)))


def recurrence(q, k, v, g, beta):
    """The reference's, with the last state read the way it can be: the state
    is linear in what it answers, so one-hot queries after the last position
    that neither decay nor write read it out, a row a query."""
    dk = q.shape[-1]
    b, _, heads = g.shape
    probes = jnp.broadcast_to(jnp.eye(dk)[None, :, None, :], (b, dk, heads, dk))
    still = lambda a, fill: jnp.concatenate(
        [a, jnp.full((b, dk) + a.shape[2:], fill, a.dtype)], axis=1)
    out = reference._recurrence(jnp.concatenate([q, probes], axis=1), still(k, 0.0), still(v, 0.0),
                                still(g, 0.0), still(beta, 0.0))
    return out[:, :-dk], out[:, -dk:].transpose(0, 2, 1, 3)


# (the case; workers or None; batch; length; heads; chunk; chunks a tile; Dk; Dv)
SHAPES = [("one-tile", None, 1, 64, 1, 16, 4, 32, 32),
          ("several-tiles", None, 1, 128, 1, 16, 2, 32, 32),
          ("several-heads-a-batch", None, 2, 96, 3, 8, 4, 16, 32),
          ("workers-vmapped", 2, 2, 64, 2, 16, 2, 32, 16),
          ("the-cells-widths", None, 1, 256, 2, 64, 2, 128, 128)]


@pytest.mark.parametrize("oracle", ["chunked", "recurrence"])
@pytest.mark.parametrize("case,workers,batch,length,heads,chunk,tile_chunks,dk,dv", SHAPES,
                         ids=[shape[0] for shape in SHAPES])
def test_kernel_is_the_gated_delta_rule(case, workers, batch, length, heads, chunk, tile_chunks,
                                        dk, dv, oracle):
    """Output, last state and the gradients of q, k, v, g and beta of a seeded
    scalar of BOTH outputs, within 2e-5 of each oracle's largest entry."""
    lead = ((workers,) if workers else ()) + (batch,)
    inputs = seeded(lead, length, heads, dk, dv)
    kernel = lambda *args: delta_rule.fused_delta_rule(*args, chunk, tile_chunks)
    theirs = (lambda *args: qwen3_next.chunked_delta_rule(*args, chunk)) \
        if oracle == "chunked" else recurrence
    (_, (out, state)), grads = scalar_and_gradients(kernel, workers)(*inputs)
    (_, (ref_out, ref_state)), ref_grads = scalar_and_gradients(theirs, workers)(*inputs)
    assert out.shape == lead + (length, heads, dv) and state.shape == lead + (heads, dk, dv)
    assert gap(out, ref_out) < 2e-5 and gap(state, ref_state) < 2e-5
    for name, ours, wanted in zip(("q", "k", "v", "g", "beta"), grads, ref_grads):
        assert ours.shape == wanted.shape
        assert gap(ours, wanted) < 2e-5, (name, gap(ours, wanted))


def test_a_heads_state_starts_empty_at_its_first_tile():
    """Two heads, two batch entries, two tiles each, different inputs: each
    (batch entry, head) alone through the kernel gives what it gives among the
    others — nothing of the state scratch, which outlives a grid step, leaks
    from the head before."""
    q, k, v, g, beta, _, _ = seeded((2,), 64, 2, 32, 32, seed=11)
    together = delta_rule.fused_delta_rule(q, k, v, g, beta, 16, 2)
    for b in range(2):
        for h in range(2):
            alone = delta_rule.fused_delta_rule(
                *(a[b:b + 1, :, h:h + 1] for a in (q, k, v, g, beta)), 16, 2)
            np.testing.assert_array_equal(together[0][b, :, h], alone[0][0, :, 0])
            np.testing.assert_array_equal(together[1][b, h], alone[1][0, 0])
    assert float(jnp.max(jnp.abs(together[1][0, 0] - together[1][1, 1]))) > 1e-3


def test_the_last_states_cotangent_is_honoured():
    """A scalar of the last state ALONE (the output's cotangent zero): the
    kernel's five gradients are the XLA form's, and not zero."""
    inputs = seeded((1,), 64, 2, 32, 32, seed=7)[:5]
    weight = jax.random.normal(jax.random.PRNGKey(8), (1, 2, 32, 32))
    of_state = lambda rule: jax.grad(
        lambda *args: jnp.sum(rule(*args)[1] * weight), argnums=(0, 1, 2, 3, 4))(*inputs)
    ours = of_state(lambda *args: delta_rule.fused_delta_rule(*args, 16, 2))
    theirs = of_state(lambda *args: qwen3_next.chunked_delta_rule(*args, 16))
    for name, mine, wanted in zip(("q", "k", "v", "g", "beta"), ours, theirs):
        if name == "q":   # no query reads the last state
            assert float(jnp.max(jnp.abs(mine))) == float(jnp.max(jnp.abs(wanted))) == 0.0
        else:
            assert float(jnp.max(jnp.abs(wanted))) > 1e-3 and gap(mine, wanted) < 2e-5, name


def test_the_chooser_answers_by_platform_and_shape(monkeypatch):
    """Off a TPU: the XLA form, whatever the shape.  On one (steered): the
    kernel at the cell's shape; the XLA form for a ragged length, a length of an
    odd count of chunks, a width that is not whole lanes and a chunk the kernel
    does not take.  The seam forces either, and refuses a shape no tile divides."""
    cell = (4096, 64, 128, 128)
    assert delta_rule.delta_rule_form(*cell) == "xla"
    monkeypatch.setattr(delta_rule.hw, "on_tpu", lambda: True)
    assert delta_rule.delta_rule_form(*cell) == "kernel"
    assert delta_rule.tile_chunks_for(4096, 64) == delta_rule.TILE_CHUNKS
    assert delta_rule.tile_chunks_for(256, 64) == 4      # a shorter sequence: one tile
    for length, chunk, dk, dv in [(4000, 64, 128, 128), (4096 + 64, 64, 128, 128),
                                  (192, 64, 128, 128), (4096, 64, 96, 128), (4096, 64, 128, 64),
                                  (4096, 32, 128, 128), (4096, 128, 128, 128)]:
        assert delta_rule.delta_rule_form(length, chunk, dk, dv) == "xla", (length, chunk, dk, dv)
    monkeypatch.undo()
    with delta_rule.forced_form("kernel"):
        assert delta_rule.delta_rule_form(64, 16, 32, 32) == "kernel"
        with pytest.raises(ValueError, match="whole tiles"):
            delta_rule.delta_rule_form(72, 16, 32, 32)
        with delta_rule.forced_form("xla"):
            assert delta_rule.delta_rule_form(*cell) == "xla"
        assert delta_rule.delta_rule_form(*cell) == "kernel"
    assert delta_rule.delta_rule_form(*cell) == "xla"
    with pytest.raises(ValueError, match="'kernel' or 'xla'"):
        with delta_rule.forced_form("pallas"):
            pass
    with pytest.raises(ValueError, match="whole tiles"):
        delta_rule.fused_delta_rule(*seeded((1,), 48, 1, 16, 16)[:5], 16, 2)


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_the_models_entry_goes_where_the_chooser_says(form, monkeypatch):
    """``qwen3_next.delta_rule`` — what ``gated_delta_net`` calls — hands the
    XLA form's result over off a TPU, a ragged length's too, and the kernel's
    inside the seam: a ``pallas_call`` of each name in the traced gradient."""
    inputs = seeded((1,), 64, 2, 32, 32, seed=13)[:5]
    scalar = lambda *args: jnp.sum(qwen3_next.delta_rule(*args, 16)[0])
    with delta_rule.forced_form(form):
        text = str(jax.make_jaxpr(jax.grad(scalar, argnums=(0, 1, 2, 3, 4)))(*inputs))
        out, state = qwen3_next.delta_rule(*inputs, 16)
    assert ("delta_rule_fwd" in text and "delta_rule_bwd" in text) is (form == "kernel")
    ref_out, ref_state = qwen3_next.chunked_delta_rule(*inputs, 16)
    assert gap(out, ref_out) < 2e-5 and gap(state, ref_state) < 2e-5
    ragged = [a[:, :50] for a in inputs]
    np.testing.assert_array_equal(qwen3_next.delta_rule(*ragged, 16)[0],
                                  qwen3_next.chunked_delta_rule(*ragged, 16)[0])


def test_the_traced_kernel_step_holds_no_narrow_operand():
    """The entry and its backward pass at the cell's chunk and widths, traced
    with the kernel forced: grid/check.py's count of products with an operand
    narrower than float32 — the kernels' bodies included — reads 0, and
    there ARE products to count."""
    check = grid_module((), "check")
    inputs = seeded((1,), 128, 1, 128, 128)[:5]

    def scalar(*args):
        out, state = qwen3_next.delta_rule(*args, 64)
        return jnp.sum(out) + jnp.sum(state)

    with delta_rule.forced_form("kernel"):
        jaxpr = jax.make_jaxpr(jax.grad(scalar, argnums=(0, 1, 2, 3, 4)))(*inputs).jaxpr
    assert check._count_narrow(jaxpr, 32) == 0
    assert check._count_narrow(jaxpr, 64) > 40      # every product is seen, and is float32


def test_the_check_scripts_column_runs_off_the_chip():
    """scripts/pallas_tpu_check.py ``--columns delta`` at a small size, the
    kernels interpreted: one row, seven quantities at both precisions, the
    kernel no further from the XLA form than the XLA form from the recurrence
    (all three are float32 sums in another order here), and a refusal to time
    an interpreter without being told to."""
    scripts = os.path.join(ROOT, "scripts")
    sys.path.insert(0, scripts)
    try:
        import pallas_tpu_check
    finally:
        sys.path.remove(scripts)
    rows = []
    failed = pallas_tpu_check.run_delta_check(
        reps=1, workers=2, length=64, heads=2, width=32, chunk=16, allow_interpret=True,
        emit=rows.append)
    assert failed == [] and len(rows) == 1 and rows[0]["parity"] == "ok", rows
    assert rows[0]["quantities"] == ["o", "state", "dq", "dk", "dv", "dg", "dbeta"]
    for precision in ("highest", "default"):
        assert len(rows[0]["gap_" + precision]) == 7 and max(rows[0]["gap_" + precision]) < 2e-5
        assert len(rows[0]["xla_from_recurrence_" + precision]) == 6
    with pytest.raises(RuntimeError, match="needs a TPU backend"):
        pallas_tpu_check.run_delta_check(reps=1)
