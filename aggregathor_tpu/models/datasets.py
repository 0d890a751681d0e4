"""Input pipelines: real data when present, deterministic synthetic otherwise.

The reference pulls MNIST through keras' downloader
(experiments/mnist.py:51-81) and CIFAR-10 from TF-Slim TFRecords on local
disk (experiments/cnnet.py:115-146).  This environment has zero egress, so
each loader first looks for a local ``.npz`` file (search order: the
``AGGREGATHOR_DATA`` env dir, ``~/.aggregathor/data``, ``./data``) and
otherwise *derives a deterministic synthetic stand-in*: class-conditional
Gaussian images whose per-class means are fixed random templates.  The
synthetic sets are honestly learnable (a linear model separates them), which
is exactly what the convergence smoke tests need, and every consumer is told
which flavour it got via ``.synthetic``.

File formats accepted: ``mnist.npz`` with x_train/y_train/x_test/y_test (the
keras layout), ``cifar10.npz`` with the same keys.

All pipelines are numpy-side (host) and hand worker-major device batches to
the engine; on TPU the transfer is one host->device copy per step, the
equivalent of the reference's dataset-on-task-CPU placement (graph.py:248-252).
"""

import functools
import os
import threading

import numpy as np

from ..obs import trace
from ..utils import UserException, can_access, info, warning

# --------------------------------------------------------------------- #
# Sharded host gather: the ~250 MB-per-chunk fancy-index gather of
# ``WorkerBatchIterator.next_many`` split into contiguous row ranges
# written concurrently via ``np.take(..., out=...)``.  The reference hid
# this work behind TF queue-runner fetcher/batcher thread pools
# (experiments/cnnet.py:115-146); this is the numpy-side equivalent, and
# with ``out=`` there is also no fresh ~250 MB allocation per chunk.

#: rows below this skip the pool entirely (thread dispatch costs more than
#: the copy it would parallelize)
_GATHER_POOL_MIN_ROWS = 4096

_gather_pool = None
_gather_pool_lock = threading.Lock()


def gather_threads():
    """Worker count for the sharded gather pool: ``AGGREGATHOR_GATHER_THREADS``
    or min(4, cpu_count).  0/1 disables the pool (single-shot gather)."""
    env = os.environ.get("AGGREGATHOR_GATHER_THREADS")
    if env is not None:
        try:
            return max(0, int(env))
        except ValueError:
            raise UserException(
                "AGGREGATHOR_GATHER_THREADS must be an integer (got %r)" % env
            )
    return min(4, os.cpu_count() or 1)


def _pool():
    global _gather_pool
    if _gather_pool is None:
        with _gather_pool_lock:
            if _gather_pool is None:
                from concurrent.futures import ThreadPoolExecutor

                _gather_pool = ThreadPoolExecutor(
                    max_workers=gather_threads(), thread_name_prefix="gather"
                )
    return _gather_pool


def sharded_take(src, indices, out):
    """``out[:] = src[indices]`` with the row copies sharded over the gather
    pool.  Bit-identical to the fancy index by construction (``np.take``
    writes the same rows; shards are disjoint contiguous ranges of ``out``).
    Falls back to one single-shot ``np.take`` for small gathers or when the
    pool is disabled."""
    nb = gather_threads()
    rows = indices.shape[0]
    if nb <= 1 or rows < _GATHER_POOL_MIN_ROWS:
        np.take(src, indices, axis=0, out=out)
        return out
    bounds = np.linspace(0, rows, nb + 1).astype(np.int64)
    futures = [
        _pool().submit(np.take, src, indices[lo:hi], 0, out[lo:hi])
        for lo, hi in zip(bounds[:-1], bounds[1:]) if hi > lo
    ]
    for future in futures:
        future.result()  # re-raises a shard's failure
    return out


def supports_buffered_next_many(iterator):
    """True when ``iterator.next_many`` accepts the ``out=`` buffer the
    ChunkPipeline's ping-pong gather needs.  Plugin iterators that copied
    the pre-pipeline ``next_many(k)`` signature stay on the legacy
    whole-chunk prefetch path instead of crashing in the producer."""
    next_many = getattr(iterator, "next_many", None)
    if next_many is None:
        return False
    import inspect

    try:
        return "out" in inspect.signature(next_many).parameters
    except (TypeError, ValueError):
        return False


def transform_is_stateless(transform):
    """True when ``transform`` declared itself stateless (``.stateless``):
    its output depends only on its inputs — it draws no RNG and keeps no
    call-count state — so skipping batches never needs to invoke it and
    batches may be produced out of order (models/preprocessing.py marks the
    identity tier; custom transforms opt in via ``stateless(fn)``)."""
    return transform is None or bool(getattr(transform, "stateless", False))


def _data_dirs():
    dirs = []
    env = os.environ.get("AGGREGATHOR_DATA")
    if env:
        dirs.append(env)
    dirs.append(os.path.expanduser("~/.aggregathor/data"))
    dirs.append(os.path.join(os.getcwd(), "data"))
    return dirs


def _find_npz(basename, subdirs=None):
    """Probe <data>/<basename> plus <data>/<subdir>/<basename> for each
    candidate subdir (default: the basename's stem — where the CIFAR-10
    TFRecord fallback writes its cache; ImageNet passes 'imagenet' since its
    cache name carries size/cap suffixes the shard directory does not)."""
    stem = basename.split(".")[0]
    subdirs = (stem,) if subdirs is None else tuple(subdirs)
    for dirname in _data_dirs():
        for path in [os.path.join(dirname, basename)] + [
            os.path.join(dirname, sub, basename) for sub in subdirs
        ]:
            if os.path.isfile(path):
                return path
    return None


class ArrayDataset:
    """An in-memory labeled dataset split into train/test."""

    def __init__(self, x_train, y_train, x_test, y_test, nb_classes, synthetic):
        self.x_train = x_train
        self.y_train = y_train
        self.x_test = x_test
        self.y_test = y_test
        self.nb_classes = nb_classes
        self.synthetic = synthetic


def data_host(rows_and_bytes):
    """Decorator for whatever materialises a training set on the host: its
    call is a ``startup.data_host`` of the start-up record (obs/trace.py),
    with ``rows`` and ``bytes`` of the training split as
    ``rows_and_bytes(result)`` gives them."""
    def decorate(load):
        @functools.wraps(load)
        def loading(*args, **kwargs):
            with trace.startup("startup.data_host", loader=load.__name__) as span:
                made = load(*args, **kwargs)
                rows, nbytes = rows_and_bytes(made)
                span.note(rows=int(rows), bytes=int(nbytes))
            return made
        return loading
    return decorate


_dataset_host = data_host(
    lambda dataset: (len(dataset.x_train), dataset.x_train.nbytes + dataset.y_train.nbytes))


def _synthetic_classification(name, shape, nb_classes, nb_train, nb_test, seed, separation=2.0):
    """Class-conditional Gaussians around fixed random unit templates."""
    rng = np.random.default_rng(seed)
    templates = rng.normal(size=(nb_classes,) + shape).astype(np.float32)
    templates /= np.linalg.norm(templates.reshape(nb_classes, -1), axis=1).reshape((-1,) + (1,) * len(shape))

    def make(count, split_seed):
        r = np.random.default_rng(split_seed)
        labels = r.integers(0, nb_classes, size=count)
        noise = r.normal(size=(count,) + shape).astype(np.float32)
        images = separation * templates[labels] + noise
        return images.astype(np.float32), labels.astype(np.int32)

    x_train, y_train = make(nb_train, seed + 1)
    x_test, y_test = make(nb_test, seed + 2)
    warning(
        "Dataset %r not found on disk; using a deterministic synthetic stand-in "
        "(drop an %s.npz under $AGGREGATHOR_DATA to use real data)" % (name, name)
    )
    return ArrayDataset(x_train, y_train, x_test, y_test, nb_classes, synthetic=True)


def _head_size(requested, y_train, y_test, name):
    """Class count for the model head: covers BOTH the requested class count
    and every label actually observed (train AND test).  Sizing from the
    train subset's max alone would let take_along_axis clamp out-of-range
    labels into silently wrong nll/accuracy (ADVICE r3); one shared helper so
    the decode path and the npz-cache path can never disagree about the head."""
    # train-only caches / limit_test=0 yield empty splits: np.max over a
    # zero-size array has no identity, so only non-empty splits vote
    seen = max(
        [int(np.max(y)) + 1 for y in (y_train, y_test) if np.size(y)] or [1]
    )
    if requested and seen < requested:
        warning(
            "%s labels only cover %d of the requested %d classes; keeping the "
            "%d-way head (subset accuracy is not full-dataset accuracy)"
            % (name, seen, requested, requested)
        )
    return max(int(requested or 0), seen)


def _load_npz(path, shape, scale, nb_classes=None):
    import zipfile

    try:
        data = np.load(path)
    except (OSError, ValueError, zipfile.BadZipFile) as exc:
        # A clear startup message instead of a mid-pipeline traceback, like
        # the reference's up-front dir validation (tools/access.py); covers
        # unreadable files AND corrupt/truncated archives.
        raise UserException("Cannot load dataset %r: %s" % (path, exc))
    def prep(x):
        x = x.astype(np.float32) / scale
        return x.reshape((x.shape[0],) + shape)
    info("Loaded dataset from %s" % path)
    y_train = data["y_train"].astype(np.int32).ravel()
    y_test = data["y_test"].astype(np.int32).ravel()
    return ArrayDataset(
        prep(data["x_train"]), y_train, prep(data["x_test"]), y_test,
        nb_classes=_head_size(nb_classes, y_train, y_test, os.path.basename(path)),
        synthetic=False,
    )


@_dataset_host
def load_mnist():
    """28x28x1 digits in [0, 1]; real file or synthetic stand-in."""
    path = _find_npz("mnist.npz")
    if path:
        return _load_npz(path, (28, 28, 1), 255.0, nb_classes=10)
    return _synthetic_classification("mnist", (28, 28, 1), 10, nb_train=8192, nb_test=2048, seed=7)


@_dataset_host
def load_digits8x8(train_fraction=0.8, seed=11):
    """REAL handwritten digits: the UCI ML hand-written digits set (1797
    8x8 grayscale images, 10 classes) bundled INSIDE scikit-learn — the one
    real vision dataset reachable on a zero-egress box.

    Same role as the reference's real-MNIST path (experiments/mnist.py:51-81
    downloads via keras): a genuine accuracy target instead of a synthetic
    stand-in.  Deterministic seeded shuffle then an 80/20 split; pixels are
    0..16 ints, normalized to [0, 1].  Resolution order: a digits.npz under
    $AGGREGATHOR_DATA (so the _synthetic_classification recovery hint is a
    live path), then sklearn, then the synthetic stand-in (flagged via
    ``.synthetic``), mirroring the 1797-image corpus at the same split.
    """
    path = _find_npz("digits.npz")
    if path:
        return _load_npz(path, (8, 8, 1), 16.0, nb_classes=10)
    nb_train = int(1797 * train_fraction)
    try:
        from sklearn.datasets import load_digits as _sk_load_digits
    except ImportError:
        return _synthetic_classification(
            "digits", (8, 8, 1), 10, nb_train=nb_train, nb_test=1797 - nb_train,
            seed=seed)
    bunch = _sk_load_digits()
    images = (bunch.images.astype(np.float32) / 16.0).reshape(-1, 8, 8, 1)
    labels = bunch.target.astype(np.int32)
    order = np.random.default_rng(seed).permutation(len(labels))
    images, labels = images[order], labels[order]
    split = int(len(labels) * train_fraction)
    info("Loaded REAL sklearn digits: %d train / %d test" % (split, len(labels) - split))
    return ArrayDataset(
        images[:split], labels[:split], images[split:], labels[split:],
        nb_classes=10, synthetic=False,
    )


@_dataset_host
def load_digits_upscaled(size=32, train_fraction=0.8, seed=11):
    """The REAL digits corpus upscaled to ``size``x``size`` (nearest-
    neighbor, integer factor) — conv-topology input on real data.

    Purpose (VERDICT r4 task 3): the reference's flagship experiment is a
    conv net on real CIFAR-10 (experiments/cnnet.py:115-146), but the real
    CIFAR bytes are unobtainable on this zero-egress box (the reference's
    own dataset symlinks dangle — docs/robustness.md "Why not real
    CIFAR-10").  Nearest-neighbor upscaling adds no information, so
    accuracies here measure the conv stack on genuine handwriting, not an
    interpolation artifact."""
    base = load_digits8x8(train_fraction=train_fraction, seed=seed)
    if size % 8:
        raise ValueError("size must be a multiple of 8 (got %d)" % size)
    k = size // 8

    def up(x):
        return np.repeat(np.repeat(x, k, axis=1), k, axis=2)

    return ArrayDataset(
        up(base.x_train), base.y_train, up(base.x_test), base.y_test,
        nb_classes=base.nb_classes, synthetic=base.synthetic,
    )


def _find_cifar10_tfrecords():
    from .tfrecord import has_cifar10_tfrecords

    for dirname in _data_dirs():
        for candidate in (dirname, os.path.join(dirname, "cifar10")):
            if has_cifar10_tfrecords(candidate):
                if not can_access(candidate, read=True):
                    warning("CIFAR-10 shards at %r are not readable; skipping" % candidate)
                    continue
                return candidate
    return None


@_dataset_host
def load_cifar10():
    """32x32x3 images in [0, 1]; real data (npz, or the reference's slim
    TFRecord shards — experiments/cnnet.py:115-146) or synthetic stand-in."""
    path = _find_npz("cifar10.npz")
    if path:
        return _load_npz(path, (32, 32, 3), 255.0, nb_classes=10)
    tfr_dir = _find_cifar10_tfrecords()
    if tfr_dir:
        from .tfrecord import read_cifar10_split

        x_train, y_train = read_cifar10_split(tfr_dir, "train")
        x_test, y_test = read_cifar10_split(tfr_dir, "test")
        info("Loaded CIFAR-10 TFRecord shards from %s" % tfr_dir)
        # Parsing 60k PNG records through the pure-Python codec costs minutes;
        # cache as the preferred npz so the next run short-circuits above.
        cache = os.path.join(tfr_dir, "cifar10.npz")
        try:
            np.savez_compressed(cache, x_train=x_train, y_train=y_train,
                                x_test=x_test, y_test=y_test)
            info("Cached npz at %s" % cache)
        except OSError:
            pass  # read-only data dir: pay the parse each run
        return ArrayDataset(
            x_train.astype(np.float32) / 255.0, y_train,
            x_test.astype(np.float32) / 255.0, y_test,
            # CIFAR-10 is 10 classes by definition; _head_size guards against
            # a truncated shard set whose subset misses the top labels
            nb_classes=_head_size(10, y_train, y_test, "CIFAR-10"),
            synthetic=False,
        )
    return _synthetic_classification("cifar10", (32, 32, 3), 10, nb_train=8192, nb_test=2048, seed=11)


def load_imagenet_standin(image_size=224, nb_classes=1000):
    """Synthetic ImageNet-shaped data (the slims experiments' scale axis).

    Sized for throughput benchmarking, not accuracy: 512 train images at
    224x224x3 float32 is ~300 MB of host RAM; the model only ever sees
    sampled batches so epoch coverage is irrelevant here.
    """
    return _synthetic_classification(
        "imagenet%d" % image_size, (image_size, image_size, 3), nb_classes,
        nb_train=512, nb_test=128, seed=13,
    )


def _find_imagenet_tfrecords():
    from .tfrecord import has_imagenet_tfrecords

    for dirname in _data_dirs():
        for candidate in (dirname, os.path.join(dirname, "imagenet")):
            if has_imagenet_tfrecords(candidate):
                if not can_access(candidate, read=True):
                    warning("ImageNet shards at %r are not readable; skipping" % candidate)
                    continue
                return candidate
    return None


@_dataset_host
def load_imagenet(image_size=224, nb_classes=1000, limit_train=4096, limit_test=1024):
    """REAL slim-layout TFRecord ImageNet when shards are on disk
    (reference: experiments/slims.py:98-111 + experiments/datasets/imagenet),
    decoded with PIL and resized to ``image_size``; otherwise the synthetic
    stand-in with its loud warning.

    Full ImageNet does not fit host RAM as a dense array, so the real path
    loads a DETERMINISTIC CAPPED SUBSET (first ``limit_train``/``limit_test``
    examples in shard order) — real pixels for throughput benchmarking and
    smoke accuracy, stated in the log line.  The decoded subset is cached as
    an npz next to the other dataset caches so subsequent runs skip the
    JPEG decode."""
    # The cache key encodes the caps too: a smoke run's tiny cache must not
    # silently satisfy a later request for the full benchmark subset.
    cache_name = "imagenet%d-t%d-v%d.npz" % (image_size, limit_train, limit_test)
    path = _find_npz(cache_name, subdirs=("imagenet",))
    if path:
        return _load_npz(path, (image_size, image_size, 3), 255.0, nb_classes=nb_classes)
    tfr_dir = _find_imagenet_tfrecords()
    if tfr_dir:
        from .tfrecord import read_imagenet_split

        x_train, y_train = read_imagenet_split(tfr_dir, "train", image_size, limit=limit_train)
        x_test, y_test = read_imagenet_split(tfr_dir, "validation", image_size, limit=limit_test)
        info(
            "Loaded ImageNet TFRecord shards from %s (capped subset: %d train / "
            "%d validation examples at %dx%d)"
            % (tfr_dir, len(x_train), len(x_test), image_size, image_size)
        )
        cache = os.path.join(tfr_dir, cache_name)
        try:
            np.savez_compressed(cache, x_train=x_train, y_train=y_train,
                                x_test=x_test, y_test=y_test)
            info("Cached npz at %s" % cache)
        except OSError:
            pass  # read-only data dir: pay the decode each run
        # slim ImageNet labels are 1-based with 0 = background (1001 classes
        # for the full set; the reference's --labels-offset knob exists for
        # models that drop background).  The capped subset may not contain
        # the top label ids — _head_size covers both the requested count and
        # every observed label (train AND validation).
        return ArrayDataset(
            x_train.astype(np.float32) / 255.0, y_train,
            x_test.astype(np.float32) / 255.0, y_test,
            nb_classes=_head_size(nb_classes, y_train, y_test, "ImageNet subset"),
            synthetic=False,
        )
    return load_imagenet_standin(image_size, nb_classes)


class WorkerBatchIterator:
    """Infinite iterator of worker-major batches [n_workers, batch, ...].

    Each worker draws its own i.i.d. sample stream (the reference gives each
    task its own dataset pipeline, graph.py:224-233); a per-worker seed keeps
    streams independent and runs reproducible.
    """

    def __init__(self, x, y, nb_workers, batch_size, seed=0, transform=None):
        self.x, self.y = x, y
        self.nb_workers = nb_workers
        self.batch_size = batch_size
        # one stream per worker: worker w's sample sequence is a function of
        # (seed, w) only, independent of nb_workers or other workers
        self.rngs = [np.random.default_rng([seed, w]) for w in range(nb_workers)]
        self.transform = transform

    def __iter__(self):
        return self

    def _draw_indices(self, k):
        """The (k, nb_workers, batch) index block: worker streams drawn
        batch-major exactly like ``__next__`` — every consumer of a block
        shares this one definition, so sharded/sequential gathers and
        ``skip`` can never disagree about the sample streams."""
        idx = np.empty((k, self.nb_workers, self.batch_size), dtype=np.int64)
        for step in range(k):
            for w, rng in enumerate(self.rngs):
                idx[step, w] = rng.integers(0, self.x.shape[0], size=self.batch_size)
        return idx

    def __next__(self):
        idx = self._draw_indices(1)[0]
        flat = idx.reshape(-1)
        bx = self.x[flat].reshape((self.nb_workers, self.batch_size) + self.x.shape[1:])
        by = self.y[flat].reshape(self.nb_workers, self.batch_size)
        if self.transform is not None:
            bx, by = self.transform(bx, by)
        return {"image": bx, "label": by}

    def skip(self, k):
        """Advance every worker's sample stream by ``k`` batches without
        gathering data — the resume fast-forward (cli/runner.py): after
        restoring step S, the stream must sit exactly where an
        uninterrupted run's would, so the resumed trajectory is
        bit-identical.  Stateful host transforms (preprocessing.py per-worker
        augmentation streams) must advance in lockstep, so those keep the
        full draw path; stateless transforms (``transform_is_stateless``)
        consume no per-batch randomness, so only the index streams advance —
        resuming after a long run costs index draws, not gathers."""
        k = int(k)
        if not transform_is_stateless(self.transform):
            for _ in range(k):
                next(self)
            return
        for _ in range(k):
            for rng in self.rngs:
                rng.integers(0, self.x.shape[0], size=self.batch_size)

    def alloc_chunk(self, k):
        """A preallocated (k, nb_workers, batch, ...) chunk for
        ``next_many(k, out=...)`` — the ping-pong buffers of the input
        pipeline are two of these."""
        k = int(k)
        return {
            "image": np.empty(
                (k, self.nb_workers, self.batch_size) + self.x.shape[1:], self.x.dtype
            ),
            "label": np.empty((k, self.nb_workers, self.batch_size), self.y.dtype),
        }

    def next_many(self, k, out=None):
        """K batches in one call: a (k, nb_workers, batch, ...) stack.

        Sample streams are identical to k successive ``next()`` calls (each
        batch's indices are drawn per worker in the same order; asserted by
        tests/test_input_pipeline.py).  The gather is sharded over a small
        thread pool via ``np.take(..., out=...)`` (``sharded_take``), and
        with ``out`` (an ``alloc_chunk(k)`` buffer) it re-fills the caller's
        buffer instead of allocating ~chunk-size afresh — the zero-re-copy
        half of the input pipeline (ChunkPipeline alternates two such
        buffers).  Without ``out`` a fresh chunk is allocated (still one
        sharded gather, no ``np.stack`` re-copy).

        A STATEFUL host ``transform`` (per-worker augmentation streams,
        poisoning) must see every batch in order, so that path keeps the
        per-batch draws; stateless transforms run on the gathered stack.
        """
        if not transform_is_stateless(self.transform):
            batches = [next(self) for _ in range(k)]
            stack = {
                name: np.stack([b[name] for b in batches]) for name in batches[0]
            }
            if out is not None:
                for name, value in stack.items():
                    out[name][...] = value
                return out
            return stack
        idx = self._draw_indices(k)
        flat = idx.reshape(-1)
        if out is None:
            out = self.alloc_chunk(k)
        sharded_take(self.x, flat, out["image"].reshape((-1,) + self.x.shape[1:]))
        sharded_take(self.y, flat, out["label"].reshape(-1))
        if self.transform is not None:
            # stateless: per-slice application == sequential application
            for step in range(k):
                img, lab = out["image"][step], out["label"][step]
                bx, by = self.transform(img, lab)
                if bx is not img:
                    img[...] = bx
                if by is not lab:
                    lab[...] = by
        return out


def eval_batches(x, y, nb_workers, batch_size):
    """Finite worker-major pass over an eval split (pads by wrapping)."""
    per_step = nb_workers * batch_size
    total = x.shape[0]
    for start in range(0, total, per_step):
        idx = np.arange(start, start + per_step) % total
        # mark wrapped duplicates so metric counts stay exact
        valid = (np.arange(start, start + per_step) < total)
        bx = x[idx].reshape((nb_workers, batch_size) + x.shape[1:])
        by = y[idx].reshape(nb_workers, batch_size)
        yield {"image": bx, "label": by, "valid": valid.reshape(nb_workers, batch_size)}


class _PrefetchError:
    def __init__(self, exc):
        self.exc = exc


class DevicePrefetcher:
    """Background-thread input prefetch: overlaps host-side batch assembly
    and host->device transfer with device compute.

    The reference hides its input path behind TF queue runners with
    fetcher/batcher threads and a prefetch queue (experiments/cnnet.py:115-146);
    the JAX equivalent is this double buffer: a daemon thread pulls host
    batches from ``iterator``, applies ``put`` (e.g. ``engine.shard_batch`` —
    ``jax.device_put`` is thread-safe and asynchronous), and keeps up to
    ``depth`` device-resident batches ready for the training loop.
    """

    def __init__(self, iterator, put, depth=2):
        import queue
        import threading

        self._queue = queue.Queue(maxsize=max(1, int(depth)))
        self._iterator = iterator
        self._put = put
        self._stop = threading.Event()
        self._terminal = None  # remembered end-of-stream / producer error
        self._thread = threading.Thread(target=self._run, daemon=True, name="prefetch")
        self._thread.start()

    def _run(self):
        try:
            for batch in self._iterator:
                if self._stop.is_set():
                    return
                device_batch = self._put(batch)
                if self._stop.is_set():
                    return
                self._queue.put(device_batch)
            self._queue.put(_PrefetchError(StopIteration()))
        except BaseException as exc:  # surfaced on the consumer side
            self._queue.put(_PrefetchError(exc))

    def __iter__(self):
        return self

    def __next__(self):
        if self._terminal is not None:  # iterator protocol: stay terminal
            raise self._terminal
        item = self._queue.get()
        if isinstance(item, _PrefetchError):
            self._terminal = item.exc
            raise item.exc
        return item

    def close(self):
        """Stop and join the worker; no batch stays pinned afterwards.

        The drain loop keeps the queue unblocked while the producer winds
        down (it may complete one last ``put``), then the join makes the
        shutdown terminal — no in-flight ``device_put`` can race a
        subsequent run's setup.
        """
        import queue
        import time

        self._stop.set()
        self._terminal = StopIteration()
        # bounded: a producer stuck inside the wrapped iterator cannot be
        # interrupted — it is a daemon thread and dies with the process
        deadline = time.monotonic() + 5.0
        while self._thread.is_alive() and time.monotonic() < deadline:
            try:
                while True:
                    self._queue.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=0.1)
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass


def split_chunk(chunk, nb_slices):
    """Split a (K, ...) host chunk into ``nb_slices`` contiguous step-axis
    slices (views, no copy; ``np.array_split`` boundaries, so slice shapes
    are a pure function of (K, nb_slices) — stable across chunks, one
    compiled transfer/assemble program per pipeline)."""
    leaves = list(chunk.values())
    k = leaves[0].shape[0]
    nb_slices = max(1, min(int(nb_slices), k))
    bounds = [k * i // nb_slices for i in range(nb_slices + 1)]
    return [
        {name: value[lo:hi] for name, value in chunk.items()}
        for lo, hi in zip(bounds[:-1], bounds[1:]) if hi > lo
    ]


class ChunkPipeline:
    """Three-stage pipelined host→device input for the unrolled trainer.

    Replaces the chunk-path ``DevicePrefetcher`` (measured SLOWER than
    synchronous dispatch, BENCH_r05: 2.62 vs 2.74 steps/s — its one daemon
    thread serially re-did the whole gather + one monolithic ``device_put``
    the sync path pays anyway).  Here each stage overlaps with the next
    *and* with device compute:

    1. **parallel zero-re-copy gather** — ``iterator.next_many(unroll,
       out=...)`` refills one of TWO preallocated ping-pong host buffers,
       the row copies sharded over the gather pool (``sharded_take``);
    2. **sliced transfer** — the chunk is split into ``slices`` step-axis
       slices (``split_chunk``) and each is issued as its own async
       ``put`` (= ``engine.shard_batches``), so the wire starts moving
       after the first 1/S of the chunk instead of after all of it;
    3. **device-side assemble** — ``assemble`` (= ``engine.
       assemble_batches``, a jitted concatenate compiled once) turns the
       slice transfers into the one (K, n, ...) chunk the scanned trainer
       consumes, all while the PREVIOUS chunk's scan occupies the device.

    **Aliasing safety** (the ping-pong contract): buffer ``i % 2`` is
    re-gathered for chunk ``i+2`` only after chunk ``i``'s *assembled*
    device chunk is materialized (``block_until_ready``) — at that point
    the concatenate has consumed the slice buffers, so even a zero-copy
    ``device_put`` that aliased host memory can no longer observe the
    overwrite.  Consumers therefore never receive a chunk whose backing
    store a later gather may touch.

    The producer is FINITE (``nb_chunks``) for the same reason the old
    chunk prefetcher was: it shares ``iterator`` with the caller's tail
    path, so it must consume exactly the chunks the loop will, then exit —
    after exhaustion (or ``close()``), the caller's direct ``iterator``
    use cannot race the daemon.

    Overlap is *measured*, not presumed: with a ``registry``
    (obs/metrics.py) the pipeline exports ``input_gather_seconds_total`` /
    ``input_put_seconds_total`` (producer busy time), ``input_wait_seconds_
    total`` (consumer blocked in ``__next__`` — the true input gap),
    ``input_chunks_total``, a live ``input_queue_depth`` gauge and the
    derived ``input_overlap_fraction`` (1 - wait/busy: the fraction of
    input work hidden under compute); the producer stages also emit
    ``input.gather`` / ``input.put`` trace spans next to the runner's
    ``host_gap``.
    """

    def __init__(self, iterator, unroll, nb_chunks, put, assemble,
                 depth=2, slices=4, registry=None):
        import queue

        self._iterator = iterator
        self._unroll = int(unroll)
        self._nb_chunks = int(nb_chunks)
        self._put = put
        self._assemble = assemble
        self._slices = max(1, int(slices))
        self._queue = queue.Queue(maxsize=max(1, int(depth)))
        self._stop = threading.Event()
        self._terminal = None
        self._buffers = [None, None]  # ping-pong host chunks (lazy alloc)
        self._retire = [None, None]   # assembled device chunk per buffer
        self._wait_s = 0.0
        self._gauge_depth = None
        if registry is not None:
            self._c_gather = registry.counter(
                "input_gather_seconds_total",
                "Producer time in the sharded host gather")
            self._c_put = registry.counter(
                "input_put_seconds_total",
                "Producer time issuing slice transfers + assemble")
            self._c_wait = registry.counter(
                "input_wait_seconds_total",
                "Consumer time blocked waiting for an input chunk")
            self._c_chunks = registry.counter(
                "input_chunks_total", "Chunks produced by the input pipeline")
            self._gauge_depth = registry.gauge(
                "input_queue_depth", "Device-ready input chunks queued")
            self._gauge_depth.set_function(self._queue.qsize)
            gather, put_c, wait = self._c_gather, self._c_put, self._c_wait

            def overlap_fraction():
                busy = gather.value + put_c.value
                if busy <= 0.0:
                    return 0.0
                return max(0.0, min(1.0, 1.0 - wait.value / busy))

            registry.gauge(
                "input_overlap_fraction",
                "Fraction of input-pipeline work hidden under device compute "
                "(1 - wait/busy)",
            ).set_function(overlap_fraction)
        else:
            class _Null:
                value = 0.0

                def inc(self, amount=1.0):
                    pass

            self._c_gather = self._c_put = self._c_wait = self._c_chunks = _Null()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="input-pipeline"
        )
        self._thread.start()

    # producer ---------------------------------------------------------- #

    def _run(self):
        import time

        import jax

        from ..obs import trace

        try:
            for index in range(self._nb_chunks):
                if self._stop.is_set():
                    return
                slot = index % 2
                if self._retire[slot] is not None:
                    # aliasing safety: chunk index-2's assemble must have
                    # consumed this buffer's slice transfers before regather
                    jax.block_until_ready(self._retire[slot])
                t0 = time.perf_counter()
                with trace.span("input.gather", cat="input"):
                    host = self._iterator.next_many(
                        self._unroll, out=self._buffers[slot]
                    )
                self._buffers[slot] = host
                self._c_gather.inc(time.perf_counter() - t0)
                if self._stop.is_set():
                    return
                t0 = time.perf_counter()
                with trace.span("input.put", cat="input"):
                    parts = [self._put(s) for s in split_chunk(host, self._slices)]
                    device_chunk = self._assemble(parts)
                self._c_put.inc(time.perf_counter() - t0)
                self._retire[slot] = device_chunk
                self._c_chunks.inc()
                self._queue.put(device_chunk)
            self._queue.put(_PrefetchError(StopIteration()))
        except BaseException as exc:  # surfaced on the consumer side
            self._queue.put(_PrefetchError(exc))

    # consumer ---------------------------------------------------------- #

    def __iter__(self):
        return self

    def __next__(self):
        import time

        if self._terminal is not None:  # iterator protocol: stay terminal
            raise self._terminal
        t0 = time.perf_counter()
        item = self._queue.get()
        waited = time.perf_counter() - t0
        self._c_wait.inc(waited)
        self._wait_s += waited
        if isinstance(item, _PrefetchError):
            self._terminal = item.exc
            raise item.exc
        return item

    @property
    def wait_seconds(self):
        """Total time THIS consumer spent blocked in ``__next__`` (the
        registry counter is process-cumulative across pipelines)."""
        return self._wait_s

    def close(self):
        """Stop and join the producer; afterwards the shared ``iterator``
        is exclusively the caller's again (the guardian-rollback /
        tail-handoff contract).  Same bounded drain-and-join discipline as
        ``DevicePrefetcher.close``; idempotent."""
        import queue
        import time

        self._stop.set()
        self._terminal = StopIteration()
        deadline = time.monotonic() + 5.0
        while self._thread.is_alive() and time.monotonic() < deadline:
            try:
                while True:
                    self._queue.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=0.1)
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass
        if self._gauge_depth is not None:
            self._gauge_depth.set(0.0)  # drop the qsize closure pinning us
            self._gauge_depth = None
        self._buffers = [None, None]
        self._retire = [None, None]
