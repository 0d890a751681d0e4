"""The step names its phases: every step body the engine builds runs under
``step.<phase>`` named scopes (parallel/engine.py ``PHASES``), and
``obs.profiler.phase_table`` reads from the compiled program's text which
instruction belongs to which phase — the table that cuts a device trace of the
step by phase.  ``obs.trace.TracedCallable.compiled_text`` hands it the text;
``obs.trace.span`` puts the program's host spans on the profiler's clock."""

import glob
import os
import re
import time

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
    compiles = profiler._compiles["count"]
    state, metrics = step(state, fed)
    jax.block_until_ready(metrics["total_loss"])
    assert step._cache_size() == 1 and profiler._compiles["count"] == compiles


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


# --------------------------------------------------------------------- #
# JAX's stage events, by program (obs/profiler.py ``listen_to_compiles``)


def stages_since(mark):
    return [event for event in trace.startup_record()["events"]
            if event["id"] >= mark and event["name"].startswith("compile.")]


def test_the_listener_names_each_stage_of_a_program():
    from aggregathor_tpu.utils.compile_cache import place_compile_cache

    assert place_compile_cache() is None  # a CPU: no cache, and the listener all the same
    assert profiler._compiles["registered"]
    mark = len(trace.startup_record()["events"])

    def stage_named(x):
        return x * 41.5

    jax.jit(stage_named)(jnp.ones(3))
    mine = [event for event in stages_since(mark)
            if event["args"]["program"] in ("stage_named", "jit(stage_named)")]
    assert [(event["name"], event["args"]["program"]) for event in mine] == [
        ("compile.trace", "stage_named"), ("compile.lower", "jit(stage_named)"),
        ("compile.load", "jit(stage_named)")]
    assert mine[2]["args"]["cache"] == "none" and mine[2]["args"]["retrieval_s"] is None
    # one clock with the program's own spans, and one thread
    assert all(event["thread"] == mine[0]["thread"] and event["dur_s"] >= 0 for event in mine)
    assert mine[0]["start_s"] + mine[0]["dur_s"] <= mine[1]["start_s"] + 1e-3
    assert abs(mine[2]["start_s"] + mine[2]["dur_s"] - time.perf_counter()) < 5.0


def test_a_nested_jit_is_a_child_of_the_trace_round_it():
    profiler.listen_to_compiles()
    mark = len(trace.startup_record()["events"])

    @jax.jit
    def nested_inside(x):
        return jnp.where(x > 0, x, 0.25)

    def nesting_outside(x):
        return nested_inside(x) + 1.5

    with trace.startup("startup.first_call", dispatcher="test"):
        jax.jit(nesting_outside)(jnp.ones(5))
    events = {event["id"]: event for event in trace.startup_record()["events"]}
    traces = {event["args"]["program"]: event for event in stages_since(mark)
              if event["name"] == "compile.trace"}
    outer, inner = traces["nesting_outside"], traces["nested_inside"]
    assert inner["parent"] == outer["id"]  # a child, not a sibling
    assert events[outer["parent"]]["name"] == "startup.first_call"
    assert events[traces["_where"]["parent"]]["args"]["program"] == "nested_inside"
    children = sum(event["dur_s"] for event in traces.values() if event["parent"] == outer["id"])
    assert 0 <= outer["dur_s"] - children <= outer["dur_s"]  # self time is never a sum


def test_registering_twice_hears_each_event_once():
    profiler.listen_to_compiles()
    registry = MetricsRegistry()
    profiler.install_compile_listener(registry)
    profiler.install_compile_listener(registry)
    profiler.listen_to_compiles()
    from jax._src import monitoring

    for listeners in (monitoring.get_event_listeners(),
                      monitoring.get_event_duration_listeners(),
                      monitoring.get_event_time_span_listeners()):
        assert listeners.count(profiler._monitor_listener) == 1
    families = {family.name: family for family in registry.families()}
    fed = jnp.ones(2)  # a program of its own: made before the count is read
    mark = len(trace.startup_record()["events"])
    before = families["compile_backend_total"].value
    seconds = families["compile_backend_seconds_total"].value

    def heard_once(x):
        return x - 17.25

    jax.jit(heard_once)(fed)
    mine = [event for event in stages_since(mark) if "heard_once" in event["args"]["program"]]
    assert [event["name"] for event in mine] == ["compile.trace", "compile.lower", "compile.load"]
    assert families["compile_backend_total"].value == before + 1
    assert families["compile_backend_seconds_total"].value == pytest.approx(
        seconds + mine[2]["dur_s"])


def test_the_cache_s_word_lands_on_the_load_it_was_said_of():
    """A hit, its retrieval time and a miss, as ``compiler.compile_or_get_cached``
    reports them: inside the load's span, on its thread."""
    profiler.listen_to_compiles()
    mark = len(trace.startup_record()["events"])
    said = jax.monitoring
    said.record_event("/jax/compilation_cache/cache_hits")
    said.record_event_duration_secs("/jax/compilation_cache/cache_retrieval_time_sec", 0.5)
    now = time.time()
    said.record_event_time_span(profiler.BACKEND_COMPILE_EVENT, now - 1.0, now,
                                fun_name="jit(from_the_cache)")
    said.record_event("/jax/compilation_cache/cache_misses")
    said.record_event_time_span(profiler.BACKEND_COMPILE_EVENT, now, now + 2.0,
                                fun_name="jit(compiled)")
    said.record_event_time_span(profiler.BACKEND_COMPILE_EVENT, now, now, fun_name="jit(unasked)")
    hit, miss, unasked = (event["args"] for event in stages_since(mark))
    assert hit == {"program": "jit(from_the_cache)", "cache": "hit", "retrieval_s": 0.5}
    assert miss == {"program": "jit(compiled)", "cache": "miss", "retrieval_s": None}
    assert unasked == {"program": "jit(unasked)", "cache": "none", "retrieval_s": None}


def test_the_step_lowers_to_the_same_text_with_the_record_on_or_off(monkeypatch):
    """The record is host-side: the program a step lowers to carries nothing of it."""
    def lowered():
        engine = RobustEngine(
            make_mesh(nb_workers=4, devices=jax.devices()[:4]),
            gars.instantiate("bulyan", NB_WORKERS, NB_BYZ), nb_workers=NB_WORKERS,
            batch_transform=flip)
        tx = marked_optimizer()
        state = engine.init_state(init_params(jax.random.PRNGKey(0)), tx, seed=1)
        step = engine.build_sampled_multi_step(model_loss, tx, repeat_steps=2, batch_size=BATCH)
        return step.lower(state, engine.replicate(data_set(jax.random.PRNGKey(1)))).as_text()

    profiler.listen_to_compiles()
    mark = len(trace.startup_record()["events"])
    with_record = lowered()
    assert len(trace.startup_record()["events"]) > mark
    monkeypatch.setattr(trace, "_startup_append", lambda *_args: None)
    mark = len(trace.startup_record()["events"])
    without = lowered()
    assert len(trace.startup_record()["events"]) == mark
    assert with_record == without
