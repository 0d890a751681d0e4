"""The step names its phases: every step body the engine builds runs under
``step.<phase>`` named scopes (parallel/engine.py ``PHASES``), and
``obs.profiler.phase_table`` reads from the compiled program's text which
instruction belongs to which phase — the table that cuts a device trace of the
step by phase.  ``obs.trace.TracedCallable.compiled_text`` hands it the text;
``obs.trace.span`` puts the program's host spans on the profiler's clock."""

import glob
import os
import re

import jax
import jax.numpy as jnp
import optax
import pytest

from aggregathor_tpu import gars
from aggregathor_tpu.obs import profiler, trace
from aggregathor_tpu.obs.metrics import MetricsRegistry
from aggregathor_tpu.parallel import RobustEngine, make_mesh
from aggregathor_tpu.parallel.engine import PHASES, PHASES_REVISION, phase

NB_WORKERS, NB_BYZ, BATCH, EXAMPLES = 8, 1, 4, 64


def model_loss(params, batch):
    """One convolution and one matmul, under a scope of the test's own so that
    the compiled program says which instructions are the model's."""
    with jax.named_scope("the_model"):
        hidden = jax.lax.conv_general_dilated(
            batch["image"], params["kernel"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        logits = jnp.tanh(hidden).reshape(hidden.shape[0], -1) @ params["dense"]
    return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(logits, batch["label"]))


def marked_optimizer():
    inner = optax.sgd(0.05, momentum=0.9)

    def update(updates, state, params=None):
        with jax.named_scope("the_optimizer"):
            return inner.update(updates, state, params)

    return optax.GradientTransformation(inner.init, update)


def flip(worker_batch, key):
    flipped = jnp.where(jax.random.bernoulli(key, 0.5, (worker_batch["image"].shape[0], 1, 1, 1)),
                        worker_batch["image"][:, :, ::-1], worker_batch["image"])
    return dict(worker_batch, image=flipped)


def init_params(key):
    k1, k2 = jax.random.split(key)
    return {"kernel": 0.1 * jax.random.normal(k1, (3, 3, 1, 4)),
            "dense": 0.1 * jax.random.normal(k2, (8 * 8 * 4, 10))}


def data_set(key):
    k1, k2 = jax.random.split(key)
    return {"image": jax.random.normal(k1, (EXAMPLES, 8, 8, 1)),
            "label": jax.random.randint(k2, (EXAMPLES,), 0, 10)}


def dispatched(builder, rule, nb_devices):
    """A dispatcher of the engine built by ``builder``, called once, with what
    it returned."""
    engine = RobustEngine(
        make_mesh(nb_workers=nb_devices, devices=jax.devices()[:nb_devices]),
        gars.instantiate(rule, NB_WORKERS, NB_BYZ), nb_workers=NB_WORKERS,
        worker_momentum=0.9, batch_transform=flip)
    tx = marked_optimizer()
    state = engine.init_state(init_params(jax.random.PRNGKey(0)), tx, seed=1)
    data = data_set(jax.random.PRNGKey(1))
    batch = jax.tree.map(lambda a: a[: NB_WORKERS * BATCH].reshape((NB_WORKERS, BATCH) + a.shape[1:]),
                         data)
    if builder == "build_step":
        step, fed = engine.build_step(model_loss, tx), engine.shard_batch(batch)
    elif builder == "build_multi_step":
        step = engine.build_multi_step(model_loss, tx, repeat_steps=2)
        fed = engine.shard_batch(batch)
    else:
        step = engine.build_sampled_multi_step(model_loss, tx, repeat_steps=2, batch_size=BATCH)
        fed = engine.replicate(data)
    return step, step(state, fed), (engine, tx, fed)


def instruction_lines(text, fragment):
    """{instruction name: line} of the lines of ``text`` that hold ``fragment``."""
    found = {}
    for line in text.splitlines():
        name = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = ", line)
        if name and fragment in line:
            found[name.group(1)] = line
    return found


@pytest.mark.parametrize("nb_devices", [1, 4])
@pytest.mark.parametrize("rule", ["average", "krum", "bulyan"])
@pytest.mark.parametrize("builder", ["build_step", "build_multi_step", "build_sampled_multi_step"])
def test_compiled_step_is_cut_by_phase(builder, rule, nb_devices):
    step, _out, _ = dispatched(builder, rule, nb_devices)
    text = step.compiled_text()
    table, notes = profiler.phase_table(text)
    expected = {"augment", "grad", "flatten", "perturb", "gar", "apply", "epilogue"}
    if builder == "build_sampled_multi_step":
        expected.add("sample")
    if nb_devices > 1:  # on one device the pad, the transpose and the cut are the identity
        expected |= {"reshard", "gather"}
    held = {p for p in table.values() if p is not None}
    assert expected <= held <= set(PHASES), (sorted(held), sorted(expected))
    assert set(notes) == {"soft", "inherited"}
    assert all(name in table for name in notes["soft"])
    assert all(table[name] is not None for name in notes["inherited"])

    # the model's convolutions and matmuls (one the compiler rewrote and left
    # without metadata is nobody's by name: it inherits from what feeds it)
    for opcode in (" convolution(", " dot("):
        models = [name for name, line in instruction_lines(text, opcode).items()
                  if "the_model" in line]
        assert models and {table[name] for name in models} == {"grad"}, opcode
    optimizer = instruction_lines(text, "the_optimizer")
    assert optimizer and {table[name] for name in optimizer} == {"apply"}
    if nb_devices > 1:
        exchanged = instruction_lines(text, " all-to-all(")
        assert exchanged and {table[name] for name in exchanged} == {"reshard"}
        gathered = {table[name] for name in instruction_lines(text, " all-gather(")}
        assert "gather" in gathered and gathered <= {"gather", "epilogue"}


HAND_WRITTEN = """HloModule jit_many, is_scheduled=true

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %multiply.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(many)/while/body/step.grad/vmap(transpose(jvp(net)))/mul" stack_frame_id=3}
  ROOT %add.1 = f32[8]{0} add(%multiply.1, %param_0), metadata={op_name="jit(many)/while/body/step.flatten/add" stack_frame_id=4}
}

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %negate.1 = f32[8]{0} negate(%param_0.1), metadata={op_name="jit(many)/while/body/step.apply/neg" stack_frame_id=5}
}

%wide.body (wide.param: (f32[8], f32[8])) -> (f32[8], f32[8]) {
  %wide.param = (f32[8]{0}, f32[8]{0}) parameter(0)
  %get-tuple-element.4 = f32[8]{0} get-tuple-element(%wide.param), index=0
  %get-tuple-element.7 = f32[8]{0} get-tuple-element(%wide.param), index=1
  %dynamic-update-slice.4 = f32[8]{0} dynamic-update-slice(%get-tuple-element.4, %get-tuple-element.4, %get-tuple-element.4)
  ROOT %tuple.4 = (f32[8]{0}, f32[8]{0}) tuple(%dynamic-update-slice.4, %get-tuple-element.7)
}

ENTRY %main.9 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0:T(128)} parameter(0)
  %fusion.1 = f32[8]{0:T(128)} fusion(%p), kind=kLoop, calls=%fused_computation
  %custom-call.7 = f32[8]{0} custom-call(), custom_call_target="AllocateBuffer"
  %constant.5 = s32[] constant(0)
  %dynamic-update-slice.8 = f32[8]{0} dynamic-update-slice(%custom-call.7, %fusion.1, %constant.5)
  %copy.3 = f32[8]{0:T(128)S(1)} copy(%dynamic-update-slice.8), backend_config={"flag_configs":[]}
  %sort.2 = f32[8]{0} sort(%copy.3), dimensions={0}, metadata={op_name="jit(many)/while/body/step.gar/jit(krum)/step.epilogue/sort" stack_frame_id=6}
  %tuple.3 = (f32[8]{0}, f32[8]{0}) tuple(%fusion.1, %sort.2)
  %while.4 = (f32[8]{0}, f32[8]{0}) while(%tuple.3), condition=%wide.cond, body=%wide.body
  %get-tuple-element.6 = f32[8]{0} get-tuple-element(%while.4), index=1
  %negate.9 = f32[8]{0} negate(%get-tuple-element.6), metadata={op_name="jit(many)/while/body/step.gather/neg" stack_frame_id=8}
  %get-tuple-element.5 = f32[8]{0} get-tuple-element(%while.4), index=0
  %custom-call.9 = f32[8]{0} custom-call(), custom_call_target="AllocateBuffer"
  %add.9 = s32[] add(%constant.5, %constant.5), metadata={op_name="jit(many)/while/body/add" stack_frame_id=7}
  ROOT %fusion.2 = f32[8]{0:T(128)} fusion(%get-tuple-element.5), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(many)/while/body/step.apply/neg" stack_frame_id=5}
}
"""


def test_phase_table_of_a_hand_written_program():
    table, notes = profiler.phase_table(HAND_WRITTEN)
    # a fusion with no metadata of its own takes its fused computation's root's
    # phase, and is listed as soft when its instructions are of two phases
    assert table["fusion.1"] == "flatten" and notes["soft"] == ["fusion.1"]
    assert table["fusion.2"] == "apply" and table["multiply.1"] == "grad"
    # the innermost scope wins
    assert table["sort.2"] == "epilogue"
    # a piece of a decomposed operation is booked to what feeds it, a copy to
    # what it feeds
    assert table["dynamic-update-slice.8"] == "flatten" and table["copy.3"] == "epilogue"
    # a loop the compiler made of a relayout is booked to what reads the element
    # it changes (not the one it only passes through), and its body to the loop
    assert table["while.4"] == "apply" and table["dynamic-update-slice.4"] == "apply"
    assert {"dynamic-update-slice.8", "copy.3", "while.4", "dynamic-update-slice.4"} <= set(
        notes["inherited"])
    # an instruction whose op_name names no phase, or a bare one next to
    # nothing, keeps None; what takes no device time is never booked
    assert table["add.9"] is None and table["custom-call.9"] is None
    assert table["get-tuple-element.5"] is None and table["tuple.3"] is None


@pytest.mark.parametrize("op_name, expected", [
    ("jit(many)/while/body/closed_call/step.grad/vmap(transpose(jvp(CNNet)))/conv1/mul", "grad"),
    ("jit(many)/step.grad/vmap(transpose(step.grad))/vmap(jvp(CNNet))/select_n", "grad"),
    ("jit(many)/while/body/step.gar/pairwise_sq_distances/pallas_call", "gar"),
    ("jit(many)/while/body/substep.gar/first_step.apply/mul", None),
    ("jit(many)/while/body/add", None),
    ("", None),
])
def test_phase_of_an_op_name(op_name, expected):
    assert profiler.phase_of(op_name) == expected


def test_a_program_with_no_phase_names_the_cache():
    unscoped = re.sub(r"step\.[a-z]+/", "", HAND_WRITTEN)
    with pytest.raises(ValueError, match="persistent compilation cache"):
        profiler.phase_table(unscoped)


def test_a_moved_scope_bumps_the_revision():
    """The persistent cache's key leaves op_name metadata out, so the programs'
    names carry ``PHASES_REVISION``: whoever adds, moves or renames a
    ``phase(...)`` bumps it, and writes the new list of call sites here."""
    import inspect

    from aggregathor_tpu.parallel import engine

    placed = re.findall(r'with phase\("(\w+)"\)', inspect.getsource(engine))
    assert (engine.PHASES_REVISION, placed) == (1, [
        "grad", "flatten",                                                  # _worker_gradients
        "augment", "perturb", "gar", "reshard", "gar", "gather", "epilogue", "apply", "epilogue",
        "sample",                                                           # the sampled trainer
        "grad", "perturb", "reshard", "gar", "gar", "apply", "epilogue",    # the sharded body
        "augment", "grad", "flatten", "perturb",                            # a bounded-wait submission
        "reshard", "gar", "apply", "epilogue",                              # the bounded-wait aggregator
    ])


def test_phase_helper_refuses_an_unknown_phase():
    with pytest.raises(KeyError):
        phase("aggregate")
    with phase("gar"):
        pass


def test_compiled_text_leaves_the_jit_cache_alone():
    step, (state, _metrics), (_engine, _tx, fed) = dispatched("build_multi_step", "krum", 4)
    assert step in trace.dispatchers()
    assert step._cache_size() == 1
    text = step.compiled_text()
    assert text.startswith("HloModule jit_many_p%d," % PHASES_REVISION)
    assert step._cache_size() == 1
    profiler.install_compile_listener(MetricsRegistry())
    compiles = profiler._monitor["count"]
    state, metrics = step(state, fed)
    jax.block_until_ready(metrics["total_loss"])
    assert step._cache_size() == 1 and profiler._monitor["count"] == compiles


def test_compiled_text_before_the_first_call_is_refused():
    fresh = trace.traced("never.dispatch", jax.jit(lambda x: x + 1))
    with pytest.raises(RuntimeError, match="not been called"):
        fresh.compiled_text()
    # a call under a trace dispatches nothing and leaves no signature behind
    jax.make_jaxpr(fresh)(jax.ShapeDtypeStruct((4,), jnp.float32))
    with pytest.raises(RuntimeError, match="not been called"):
        fresh.compiled_text()
    fresh(jnp.ones(4))
    assert fresh.compiled_text().startswith("HloModule jit__lambda")


def test_program_spans_reach_the_profiler_with_no_tracer_installed(tmp_path):
    from jax.profiler import ProfileData

    assert trace.installed() is None
    doubled = trace.traced("phase_test.dispatch", jax.jit(lambda x: 2 * x))
    doubled(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("phase_test.span", cat="train"):
            doubled(jnp.ones(4)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    written = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    assert written
    names = {event.name
             for plane in ProfileData.from_file(written[-1]).planes if plane.name.startswith("/host:")
             for line in plane.lines for event in line.events}
    assert {"phase_test.span", "phase_test.dispatch"} <= names
