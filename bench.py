"""Benchmark harness: robust training throughput, reference-protocol timing.

Times BASELINE.json config 2 — the cnnet CIFAR-10 CNN under Multi-Krum with
n=8 workers, f=2 declared Byzantine — on the TPU this process holds, and
prints ONE JSON line.  The metric follows the reference's own definition:
steps/s EXCLUDING the first (compilation) step (reference: runner.py:595-597).

One process, and it needs the chip: with no TPU it exits non-zero and prints
no metric; a phase that fails is a traceback and a non-zero exit.  (ROADMAP
S0 replaces this file with a grid of cells; until then it is the one cell.)

Two timing modes are reported:
  - fresh-batch (HEADLINE): every scanned step consumes a distinct batch and
    the timed loop pays the host-side iterator + host->device transfer, like
    the reference's per-step loop pays its input path (runner.py:562-576).
    The headline is the scanned trainer (best of synchronous, prefetched,
    and device-sampled input sourcing — detail.headline_source says which;
    device-sampled holds the dataset on-chip, transferred once, and gathers
    each worker's fresh i.i.d. batch in-graph).  A device-sampled WIN
    renames the metric with a ``_device_input_`` infix and keeps the best
    streamed rate in detail.steps_per_s_streamed, so streamed rows are never
    compared to a different input architecture under one name; the per-step
    dispatch figure (the reference's own loop shape) stays in
    detail.per_step_dispatch;
  - resident-batch: one device-resident batch reused for all steps — the
    pure-compute upper bound.

The reference repository publishes no numbers (BASELINE.md), so
``vs_baseline`` is reported against the driver-set north-star throughput of
2000 steps/s (BASELINE.json "north_star").
"""

import json
import sys
import time

NORTH_STAR_STEPS_PER_S = 2000.0
_T0 = time.perf_counter()


def _phase(msg):
    """Timestamped progress marker (stderr, flushed)."""
    print("BENCH_PHASE %7.1fs %s" % (time.perf_counter() - _T0, msg),
          file=sys.stderr, flush=True)


def _cost_key(cost, key):
    """One key of an XLA ``cost_analysis()`` mapping as a positive float, or
    None when the backend reports no mapping or no such key."""
    value = (cost or {}).get(key)
    return float(value) if value and float(value) > 0.0 else None


def run_bench():
    """Measure config 2 on the TPU; returns the result row."""
    import jax

    from aggregathor_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    devices = jax.devices()
    _phase("devices: %s" % (devices,))
    if devices[0].platform != "tpu":
        raise SystemExit(
            "bench.py measures on a TPU; JAX found %d %s device(s) (%s)"
            % (len(devices), devices[0].platform, devices[0].device_kind))

    import numpy as np
    import optax

    from aggregathor_tpu import gars, models
    from aggregathor_tpu.models.datasets import DevicePrefetcher
    from aggregathor_tpu.parallel.engine import RobustEngine
    from aggregathor_tpu.parallel.mesh import make_mesh
    from aggregathor_tpu.utils.hw import peaks

    nb_workers, nb_byz = 8, 2
    batch_size, unroll, chunks = 128, 20, 10

    # One chip hosts all n logical workers (vmapped); more chips spread them.
    nb_devices = max(d for d in range(1, len(devices) + 1) if nb_workers % d == 0)
    mesh = make_mesh(nb_workers=nb_devices, devices=devices[:nb_devices])
    # Whole-program FLOPs and bytes vs whole-mesh peaks, by device_kind.
    chip = peaks(devices[0])
    peak = chip.bf16_flops * nb_devices
    hbm_bw = chip.hbm_bytes_per_s * nb_devices

    def sync(m):
        # the timing fence: fetch the last loss, which the whole dispatch feeds
        return float(np.asarray(m["total_loss"]).reshape(-1)[-1])

    def warm(fn, st, batch, what):
        _phase("compile+first-run: %s" % what)
        t0 = time.perf_counter()
        st, m = fn(st, batch)
        sync(m)
        dt = time.perf_counter() - t0
        _phase("compiled %s in %.1fs" % (what, dt))
        return st, dt

    def timed(dispatch, st, n_dispatch, steps_per_dispatch, what):
        _phase("timing: %s (%d x %d steps)" % (what, n_dispatch, steps_per_dispatch))
        t0 = time.perf_counter()
        m = None
        for _ in range(n_dispatch):
            st, m = dispatch(st)
        loss = sync(m)
        rate = n_dispatch * steps_per_dispatch / (time.perf_counter() - t0)
        _phase("timed %s: %.3f steps/s" % (what, rate))
        if not np.isfinite(loss):
            raise RuntimeError("%s ended on a non-finite loss" % what)
        return rate, st, loss

    result = {
        "metric": "cnnet_cifar10_multikrum_n8_f2_steps_per_s",
        "value": 0.0,
        "unit": "steps/s",
        "vs_baseline": 0.0,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
        "detail": {
            "platform": devices[0].platform,
            "nb_devices": nb_devices,
            "nb_workers": nb_workers,
            "nb_byz": nb_byz,
            "batch_size_per_worker": batch_size,
            "unroll": unroll,
        },
    }

    def measure(extra_args, detail, is_headline):
        """One measurement of config 2 (+extra args), filling ``detail``."""
        tag = "bf16" if extra_args else "f32"
        # augment:device — the cifarnet crop/flip runs INSIDE the jitted
        # step (models/preprocessing.py device tier), so the host input path
        # is only the gather + host->device transfer, like a production TPU
        # pipeline.
        experiment = models.instantiate(
            "cnnet", ["batch-size:%d" % batch_size, "augment:device"] + extra_args
        )
        gar = gars.instantiate("krum", nb_workers, nb_byz)
        engine = RobustEngine(mesh, gar, nb_workers, batch_transform=experiment.device_transform())

        tx = optax.sgd(1e-2)
        params = experiment.init(jax.random.PRNGKey(0))
        state = engine.init_state(params, tx)
        it = experiment.make_train_iterator(nb_workers, seed=0)
        resident_batch = engine.shard_batch(next(it))
        detail["augment"] = experiment.augment
        _phase("%s: model/data/state ready" % tag)

        def refresh(fresh_rate, source, steps):
            # timed_steps always describes the HEADLINE source's own sample
            # size (8 for the per-step loop, unroll*n_chunks for scanned)
            detail["steps_per_s_fresh_batch"] = round(fresh_rate, 3)
            detail["headline_source"] = source
            detail["timed_steps"] = steps
            if detail.get("flops_per_step"):
                key = "mfu_pct" if extra_args else "mfu_pct_of_bf16_peak"
                detail[key + "_fresh"] = round(
                    100.0 * detail["flops_per_step"] * fresh_rate / peak, 2)
            if is_headline:
                result["value"] = round(fresh_rate, 3)
                result["vs_baseline"] = round(fresh_rate / NORTH_STAR_STEPS_PER_S, 4)

        # --- Phase a: per-step dispatch (the reference's own loop shape,
        # runner.py:562-576).
        step_fn = engine.build_step(experiment.loss, tx)
        state, first = warm(step_fn, state, resident_batch, tag + " 1-step program")
        detail["first_step_s"] = round(first, 3)
        per_step_fresh, state, loss = timed(
            lambda st: step_fn(st, engine.shard_batch(next(it))),
            state, 8, 1, tag + " per-step fresh")
        detail["final_loss"] = loss
        detail["per_step_dispatch"] = {
            "steps_per_s_fresh_batch": round(per_step_fresh, 3), "timed_steps": 8}
        refresh(per_step_fresh, "per_step_dispatch", 8)

        # --- Phase b: per-step FLOPs from XLA's cost model, on the SINGLE-
        # step program: the scanned trainer's while-body is counted once by
        # HloCostAnalysis regardless of trip count, so analyzing the K-step
        # program would understate per-step FLOPs ~Kx.  Lowered-stage
        # analysis only (host-side trace, no device compile).
        cost = step_fn.lower(state, resident_batch).cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else None
        flops = _cost_key(cost, "flops")
        bytes_per_step = _cost_key(cost, "bytes accessed")
        if flops:
            detail["flops_per_step"] = flops
        if bytes_per_step:
            detail["bytes_per_step"] = bytes_per_step
            detail["hbm_roofline_steps_per_s"] = round(hbm_bw / bytes_per_step, 2)
        _phase("%s: cost analysis %s flops/step, %s bytes/step (absent: MFU omitted)"
               % (tag, flops, bytes_per_step))
        refresh(per_step_fresh, "per_step_dispatch", 8)

        # Scale timed-loop length to the observed rate so each loop stays
        # ~<=90 s even if the chip runs this program far slower than expected.
        n_chunks = max(1, min(chunks, int(max(per_step_fresh, 0.05) * 90.0 / unroll)))

        # --- Phase c: scanned fresh trainer, SYNCHRONOUS input (vectorized
        # K-batch gather + transfer on the timed path, no helper thread).
        fresh_fn = engine.build_multi_step(experiment.loss, tx)
        state, _ = warm(fresh_fn, state, engine.shard_batches(it.next_many(unroll)),
                        tag + " scanned fresh trainer (K=%d)" % unroll)
        sync_fresh, state, loss = timed(
            lambda st: fresh_fn(st, engine.shard_batches(it.next_many(unroll))),
            state, n_chunks, unroll, tag + " scanned fresh (sync input)")
        detail["final_loss"] = loss
        detail["scanned_fresh_sync"] = {
            "steps_per_s": round(sync_fresh, 3), "timed_steps": unroll * n_chunks}
        # The scanned trainer IS the headline program (docstring: fresh-batch
        # scanned loop) — it REPLACES the per-step number even if slower, so
        # the metric keeps one meaning.
        best_fresh = sync_fresh
        refresh(best_fresh, "scanned_fresh_sync", unroll * n_chunks)

        # --- Phase d: scanned fresh with the background prefetcher
        # overlapping gather+transfer with device compute (the reference's
        # queue runners played this role, experiments/cnnet.py:115-146).
        # Same compiled program as phase c.
        def chunks_iter():
            while True:
                yield it.next_many(unroll)

        prefetcher = DevicePrefetcher(chunks_iter(), engine.shard_batches, depth=2)
        try:
            prefetch_fresh, state, _ = timed(
                lambda st: fresh_fn(st, next(prefetcher)),
                state, n_chunks, unroll, tag + " scanned fresh (prefetched)")
        finally:
            prefetcher.close()  # keep later timings free of producer work
        detail["scanned_fresh_prefetch"] = {
            "steps_per_s": round(prefetch_fresh, 3), "timed_steps": unroll * n_chunks}
        # Different input sourcing of one program: the headline takes the
        # better of the two; both numbers stay in detail.
        if prefetch_fresh > best_fresh:
            best_fresh = prefetch_fresh
            refresh(best_fresh, "scanned_fresh_prefetch", unroll * n_chunks)

        # --- Phase d2: scanned fresh, DEVICE-SAMPLED input — the dataset
        # lives on the chip (transferred once) and each step gathers a fresh
        # i.i.d. per-worker batch in-graph (engine.build_sampled_multi_step).
        # Still a fresh-batch trainer (same stream semantics as the host
        # iterator), so it is headline-eligible.
        arrays = experiment.train_arrays()
        if arrays is not None:  # None = a host transform must see each batch
            # The best STREAMED rate (sync/prefetched — both pay the host
            # iterator + host->device transfer, like the reference's input
            # architecture) is recorded unconditionally, so comparisons stay
            # like-for-like when the device-sampled program wins the headline.
            detail["steps_per_s_streamed"] = round(best_fresh, 3)
            sampled_fn = engine.build_sampled_multi_step(
                experiment.loss, tx, repeat_steps=unroll, batch_size=batch_size)
            dataset = engine.replicate(arrays)
            state, _ = warm(sampled_fn, state, dataset,
                            tag + " scanned fresh trainer (device-sampled)")
            sampled_fresh, state, loss = timed(
                lambda st: sampled_fn(st, dataset),
                state, n_chunks, unroll, tag + " scanned fresh (device-sampled)")
            detail["final_loss"] = loss
            detail["scanned_fresh_sampled"] = {
                "steps_per_s": round(sampled_fresh, 3), "timed_steps": unroll * n_chunks}
            if sampled_fresh > best_fresh:
                best_fresh = sampled_fresh
                if is_headline:
                    # A device-sampled headline measures a different input
                    # architecture than a streamed one; the NAME says so.
                    result["metric"] = "cnnet_cifar10_multikrum_n8_f2_device_input_steps_per_s"
                refresh(best_fresh, "scanned_fresh_sampled", unroll * n_chunks)
            del dataset  # release ~0.6 GB/device of HBM before phase e / bf16

        # --- Phase e: scanned resident trainer — one device-resident batch
        # reused for all K steps: the pure-compute upper bound.
        resident_fn = engine.build_multi_step(experiment.loss, tx, repeat_steps=unroll)
        state, _ = warm(resident_fn, state, resident_batch,
                        tag + " scanned resident trainer")
        resident_rate, state, _ = timed(
            lambda st: resident_fn(st, resident_batch),
            state, n_chunks, unroll, tag + " scanned resident")
        detail["steps_per_s_resident_batch"] = round(resident_rate, 3)
        if detail.get("flops_per_step"):
            key = "mfu_pct" if extra_args else "mfu_pct_of_bf16_peak"
            detail[key + "_resident"] = round(
                100.0 * detail["flops_per_step"] * resident_rate / peak, 2)
        if detail.get("bytes_per_step"):
            detail["pct_of_hbm_roofline_resident"] = round(
                100.0 * detail["bytes_per_step"] * resident_rate / hbm_bw, 1)

    # The f32 HEADLINE.  Note on the MFU field names: the f32 program does
    # not run at the chip's bf16 peak, so its fields say exactly which bar
    # they measure against (mfu_pct_of_bf16_peak_*); the like-for-like MFU
    # lands on the bfloat16 secondary below (mfu_pct_*).
    measure([], result["detail"], is_headline=True)

    # Secondary: bfloat16 compute (MXU-rate matmuls, f32 params).
    result["detail"]["bfloat16"] = {}
    measure(["dtype:bfloat16"], result["detail"]["bfloat16"], is_headline=False)
    return result


if __name__ == "__main__":
    print(json.dumps(run_bench()))
