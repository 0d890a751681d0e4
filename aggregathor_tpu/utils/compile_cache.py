"""Where the persistent compilation cache lives — decided from outside.

Entry points (cli.runner, cli.serve, chip_smoke.py,
scripts/pallas_tpu_check.py, grid/run.py) call :func:`place_compile_cache`
before their first compile; importing the library sets nothing and listens to
nothing.  The call also registers the process's one ``jax.monitoring``
listener (``obs.profiler.listen_to_compiles``): it is the call that decides
whether a program's load is a hit, so it is where the record of each
program's trace, lowering and load starts.  A machine that wants the
cache to outlive the process exports ``JAX_COMPILATION_CACHE_DIR`` and JAX
reads it; otherwise the cache sits at one fixed path inside the checkout
(the path is part of the cache key on some backends, so it never carries a
pid, a tempdir or a timestamp).
"""

import os

#: ``<checkout>/.jax_cache`` (git-ignored)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def place_compile_cache():
    """Point JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` when the
    environment names one (nothing to do: JAX reads it), else at
    ``DEFAULT_DIR``; returns the directory in effect, or None on a CPU
    backend, where nothing is cached (a CPU run is a test or a debug run,
    and XLA:CPU's loader logs an error block for every cached program it
    loads on a machine whose feature list differs in spelling).

    Every program is cached, however quickly it compiled: a run is dozens of
    sub-second programs around a few large ones, and a warm second process
    should compile nothing.  Initializes the backend (the platform choice
    must be pinned before the call), inside a ``startup.backend`` span of the
    start-up record: whoever touches the backend first pays for its start,
    and an entry point that has not yet pays here."""
    import jax

    from ..obs import profiler, trace

    profiler.listen_to_compiles()  # on a CPU backend too: the tests' path is the chip's
    with trace.startup("startup.backend"):
        backend = jax.default_backend()
    if backend == "cpu":
        return None
    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not directory:
        directory = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return directory
