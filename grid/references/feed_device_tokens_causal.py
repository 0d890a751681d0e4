"""Which token rows each worker trains on at each step, restated from the
program's documented stream (engine.build_sampled_multi_step): worker w's draw
at step s is a function of (run key, s, w) alone.

    step key   = fold_in(run key, s)
    rows       = randint(fold_in(fold_in(step key, w), 4), (batch,), 0, rows held)

The run key is ``PRNGKey(seed)``.  A row holds L + 1 ids: returns ``(its first
L, its last L)``, what grid/references/laguna.py ``loss`` takes as inputs and
targets.  ``augment`` is the configuration's ``"none"``: nothing is drawn
besides the rows.
"""

import functools

import jax


@functools.partial(jax.jit, static_argnames=("batch_size", "augment"))
def worker_batch(dataset, run_key, step, worker, *, batch_size, augment):
    if augment != "none":
        raise SystemExit("feed_device_tokens_causal: augment %r is not none" % augment)
    worker_key = jax.random.fold_in(jax.random.fold_in(run_key, step), worker)
    rows = jax.random.randint(jax.random.fold_in(worker_key, 4), (batch_size,), 0,
                              dataset["tokens"].shape[0])
    tokens = dataset["tokens"][rows]
    return tokens[:, :-1], tokens[:, 1:]
