"""Wire codecs: compressed robust gradient exchange (docs/engine.md, "The
wire").

At production ``n`` and ``d`` the (n, d) submission stack IS the bandwidth
bill — the reference paid it in full-precision UDP datagrams, and a bf16
wire only halves it.  This module is what the engine's one wire option
(``exchange=``) means, a wire dtype or a **wire codec**: every worker's
submission is ENCODED at the sender (after the worker-local attacks — an
attacker forges what it transmits), crosses the simulated transport as the
encoded payload (a dropped packet drops ENCODED bytes), and is DECODED at
the aggregation boundary so every GAR sees float32 rows.  OptiReduce
(arXiv:2310.06993) motivates the lever: the cloud tail is bandwidth-bound,
so fewer bytes per row is steps/s, not just a smaller bill.

Codecs (``--exchange`` on the runner; ``parse_exchange_spec`` grammar):

- ``f32``/``float32`` — the uncompressed wire (no codec, no dtype cast).
- ``bf16``/``bfloat16`` — a wire dtype, no codec: the engine's
  ``exchange_dtype``, a cast at the collective boundary, 2x.
- ``int8[:ef]`` — per-row symmetric quantization with a traced float32
  scale (``max|row| / 127``): ~3.97x at large d.  A row whose magnitude
  is non-finite cannot encode — its wire image is a NaN row, absorbed by
  the NaN-tolerant rules inside the same declared-f budget as a lossy row.
- ``topk:k=K[,ef]`` / ``topk:frac=F[,ef]`` — magnitude top-k
  sparsification (value + index per kept coordinate, ``d/(2k)``x); NaN
  coordinates sort as +inf magnitude so a poisoned coordinate still
  crosses the wire instead of silently vanishing.

``ef`` enables **error feedback** (Karimireddy et al., SignSGD/EF-style):
the worker transmits ``C(g + e)`` and carries the residual
``e' = (g + e) - C(g + e)`` so quantization error accumulates into later
submissions instead of being lost — the difference between biased
sparsification and a convergent one.  The per-worker residual rides
``TrainState.ef`` (worker-sharded, checkpointed — core/train_state.py), so
restore and guardian rollback preserve it bit-exactly.

Feasibility is validated at parse/construction time, not at step 1e6:
the fixed-point masked path (``--secure-mask``) refuses loudly (a lossy
wire would corrupt the exact mod-2^64 pad cancellation), the sharded
engine refuses (per-leaf EF state is a different protocol; bf16 stays
available there), and an infeasible ``topk`` budget refuses when ``d``
is known.  ``wire_roundtrip`` is THE one place owning the precision-loss
semantics of rows that cross the wire (forged rows are squeezed through
it exactly like honest ones — parallel/engine.py's three call sites).

Composition with bounded-wait v3's age reweighting (``--stale-reweight``):
a stale carry row is stored ENCODED (the wire payload the aggregator last
received), and the reweight coefficient c(a) = 1/(1+a) is applied by the
aggregate AFTER this module's decode — the quantization scale and the age
discount compose as two traced scalars on the decoded f32 row, so neither
the codec nor the EF residual ever sees a damped value (a stale worker's
residual is frozen by the arrived-mask write-back, engine.py).
"""

import numpy as np

from ..utils import UserException

#: wire bytes of one float32 coordinate / one float32 scalar
_F32_BYTES = 4
#: wire bytes of one int32 coordinate index (top-k payload)
_I32_BYTES = 4


def _parse_options(body):
    """``k=64,ef`` -> {"k": "64", "ef": True}; bare keys are flags."""
    options = {}
    for part in body.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" in part:
            key, value = part.split("=", 1)
            options[key.strip()] = value.strip()
        else:
            options[part] = True
    return options


def parse_exchange_spec(spec):
    """``--exchange`` spec -> ``(exchange_dtype, codec)``.

    At most one of the pair is non-None (both None for the f32 wire):
    ``bf16`` is a dtype cast at the collective boundary, no codec;
    ``int8``/``topk`` return a :class:`WireCodec`.  Accepts an already-constructed codec and passes
    it through (the test/benchmark surface)."""
    if spec is None:
        return None, None
    if isinstance(spec, WireCodec):
        return None, spec
    if not isinstance(spec, str):
        raise UserException(
            "--exchange wants a spec string or a WireCodec (got %r)" % (spec,)
        )
    name, _, body = spec.partition(":")
    name = name.strip().lower()
    options = _parse_options(body)

    def reject_options(allowed=()):
        unknown = sorted(set(options) - set(allowed))
        if unknown:
            raise UserException(
                "--exchange %s does not take option(s) %s"
                % (name, ", ".join(unknown))
            )

    if name in ("f32", "float32"):
        reject_options()
        return None, None
    if name in ("bf16", "bfloat16"):
        reject_options()
        import jax.numpy as jnp

        return jnp.dtype(jnp.bfloat16), None
    def ef_flag():
        # ef is a bare flag: an explicit value like ef=0 reads as intent
        # to DISABLE, and silently enabling would change the TrainState
        # layout behind the operator's back — refuse anything but the flag
        ef = options.get("ef", False)
        if ef is not True and ef is not False:
            raise UserException(
                "--exchange %s: ef is a bare flag — write ':...,ef' to "
                "enable error feedback, omit it to disable (got ef=%s)"
                % (name, ef)
            )
        return ef

    if name == "int8":
        reject_options(("ef",))
        return None, Int8Codec(ef=ef_flag())
    if name == "topk":
        reject_options(("k", "frac", "ef"))
        k = options.get("k")
        frac = options.get("frac")
        if (k is None) == (frac is None):
            raise UserException(
                "--exchange topk wants exactly one of k=K or frac=F "
                "(e.g. topk:k=4096,ef or topk:frac=0.0625,ef)"
            )
        try:
            k = None if k is None else int(k)
            frac = None if frac is None else float(frac)
        except ValueError:
            raise UserException("--exchange topk: k wants an int, frac a float")
        return None, TopKCodec(k=k, frac=frac, ef=ef_flag())
    raise UserException(
        "unknown --exchange spec %r (know: f32, bf16, int8[:ef], "
        "topk:k=K[,ef], topk:frac=F[,ef])" % (spec,)
    )


class WireCodec:
    """One wire codec: ``encode`` at the sender, ``decode`` at the
    aggregation boundary, ``roundtrip`` where the engine only needs the
    wire IMAGE (the fused step simulates the transport in-graph).

    All row methods take/return the LAST-axis-``d`` single row the
    submission pipeline works in; ``*_rows`` vmap over a leading worker
    axis.  ``payload`` is a pytree of arrays — what actually crosses the
    host boundary on the bounded-wait path."""

    name = "wire"
    uses_ef = False

    # -- contract ------------------------------------------------------ #

    def encode(self, row):
        raise NotImplementedError

    def decode(self, payload, d):
        raise NotImplementedError

    def bytes_per_row(self, d):
        """Wire bytes of one encoded (d,) row (payload + side channel)."""
        raise NotImplementedError

    def payload_zeros(self, d):
        """Host-side (numpy) zeroed payload for a slot nobody submitted —
        content is irrelevant (the aggregate masks missing slots to NaN
        AFTER decoding), only the pytree structure/shapes matter."""
        raise NotImplementedError

    def validate_d(self, d):
        """Refuse an infeasible codec budget once ``d`` is known."""

    # -- shared machinery ---------------------------------------------- #

    def roundtrip(self, row):
        """The wire image of one row: encode then decode, fused in-graph."""
        return self.decode(self.encode(row), row.shape[-1])

    def roundtrip_rows(self, rows):
        import jax

        return jax.vmap(self.roundtrip)(rows)

    def decode_rows(self, payload, d):
        import jax

        return jax.vmap(lambda p: self.decode(p, d))(payload)

    def ef_roundtrip(self, row, ef_row):
        """Error-feedback transmit: returns ``(wire_image, new_ef)`` where
        the image is ``C(row + ef)`` and ``new_ef`` the residual the
        worker carries into its next submission.  A non-finite wire image
        resets the residual (a NaN row must not poison every later send)."""
        _, decoded, new_ef = self.ef_encode(row, ef_row)
        return decoded, new_ef

    def ef_encode(self, row, ef_row):
        """``(payload, wire_image, new_ef)`` — the bounded-wait submission
        form (the payload crosses the host boundary, the image feeds the
        digest, the residual is written back on arrival)."""
        import jax.numpy as jnp

        target = row.astype(jnp.float32) + ef_row
        payload = self.encode(target)
        decoded = self.decode(payload, row.shape[-1])
        new_ef = jnp.where(jnp.isfinite(decoded), target - decoded,
                           jnp.zeros_like(target))
        return payload, decoded, new_ef

    def ratio(self, d):
        """Nominal compression ratio vs the f32 wire."""
        return (d * _F32_BYTES) / float(self.bytes_per_row(d))

    def validate_for(self, gar=None):
        """Construction-time feasibility (re-run on every guardian
        escalation rebuild — the engine constructs through here)."""
        if gar is not None and getattr(gar, "masking", None) is not None:
            raise UserException(
                "--secure-mask's fixed-point pairwise pads cancel exactly "
                "mod 2^64 over the EXACT float32 rows; a lossy wire codec "
                "(%s) would corrupt the cancellation into one-time-pad "
                "garbage — run masking on the f32/bf16 wire" % self.spec()
            )

    def spec(self):
        return self.name


class Int8Codec(WireCodec):
    """Per-row symmetric int8 quantization with a traced float32 scale.

    ``scale = max|row| / 127``; coordinates quantize to round(row/scale)
    in [-127, 127].  The scale rides the payload (4 bytes/row — the
    "traced scales": a per-step data value, never a compiled constant, so
    steady state never recompiles).  A row whose magnitude is non-finite
    cannot encode — int8 has no inf — and its wire image is a NaN row,
    which the NaN-tolerant rules absorb within the declared-f budget."""

    name = "int8"

    def __init__(self, ef=False):
        self.uses_ef = bool(ef)

    def encode(self, row):
        import jax.numpy as jnp

        row = row.astype(jnp.float32)
        scale = jnp.max(jnp.abs(row), axis=-1) / jnp.float32(127.0)
        safe = jnp.where((scale > 0) & jnp.isfinite(scale), scale, 1.0)
        q = jnp.clip(jnp.round(row / safe[..., None]), -127.0, 127.0)
        # a NaN coordinate would cast to an arbitrary int8: pin it to 0
        # (the whole row reads NaN at decode anyway — the scale is NaN)
        q = jnp.where(jnp.isfinite(q), q, 0.0).astype(jnp.int8)
        return {"q": q, "scale": scale}

    def decode(self, payload, d):
        import jax.numpy as jnp

        scale = payload["scale"]
        out = payload["q"].astype(jnp.float32) * scale[..., None]
        return jnp.where(jnp.isfinite(scale)[..., None], out, jnp.nan)

    def bytes_per_row(self, d):
        return d + _F32_BYTES  # 1 byte/coordinate + the f32 scale

    def payload_zeros(self, d):
        return {"q": np.zeros((d,), np.int8),
                "scale": np.zeros((), np.float32)}

    def spec(self):
        return "int8:ef" if self.uses_ef else "int8"


class TopKCodec(WireCodec):
    """Magnitude top-k sparsification: the k largest-|value| coordinates
    cross the wire as (float32 value, int32 index) pairs; everything else
    decodes to zero.  ``frac`` resolves to ``k = max(1, round(frac * d))``
    once ``d`` is known (static per engine — no recompiles).  NaN
    coordinates sort as +inf magnitude, so a poisoned coordinate is
    transmitted (and lands in the GAR's NaN accounting) instead of being
    silently zeroed by its own corruption.  Biased without error
    feedback — pass ``ef`` for training runs (docs/engine.md)."""

    name = "topk"

    def __init__(self, k=None, frac=None, ef=False):
        if k is not None and k < 1:
            raise UserException("--exchange topk wants k >= 1 (got %d)" % k)
        if frac is not None and not 0.0 < frac <= 1.0:
            raise UserException(
                "--exchange topk wants frac in (0, 1] (got %g)" % frac
            )
        self.k = None if k is None else int(k)
        self.frac = None if frac is None else float(frac)
        self.uses_ef = bool(ef)

    def _k_for(self, d):
        k = self.k if self.k is not None else max(1, int(round(self.frac * d)))
        if k > d:
            raise UserException(
                "--exchange topk: k=%d exceeds the model dimension d=%d "
                "(a sparsifier that keeps more than everything is a "
                "misconfiguration, not a wire)" % (k, d)
            )
        if k > d // 2:
            # 8 bytes per kept coordinate (f32 value + int32 index): past
            # d/2 the "compressed" payload EXCEEDS the raw f32 wire and
            # the compression_ratio gauge's >= 1 contract breaks — refuse
            # the inflation instead of shipping it silently
            raise UserException(
                "--exchange topk: k=%d > d/2 = %d INFLATES the wire (each "
                "kept coordinate ships value + index, 8 bytes vs 4 raw) — "
                "use k <= d/2, or the f32/bf16 wire if you want everything"
                % (k, d // 2)
            )
        return k

    def validate_d(self, d):
        self._k_for(d)

    def encode(self, row):
        import jax
        import jax.numpy as jnp

        row = row.astype(jnp.float32)
        k = self._k_for(row.shape[-1])
        mag = jnp.where(jnp.isnan(row), jnp.inf, jnp.abs(row))
        _, idx = jax.lax.top_k(mag, k)
        return {"v": jnp.take(row, idx), "i": idx.astype(jnp.int32)}

    def decode(self, payload, d):
        import jax.numpy as jnp

        return jnp.zeros((d,), jnp.float32).at[payload["i"]].set(payload["v"])

    def bytes_per_row(self, d):
        return self._k_for(d) * (_F32_BYTES + _I32_BYTES)

    def payload_zeros(self, d):
        k = self._k_for(d)
        return {"v": np.zeros((k,), np.float32), "i": np.zeros((k,), np.int32)}

    def spec(self):
        body = "k=%d" % self.k if self.k is not None else "frac=%g" % self.frac
        return "topk:%s%s" % (body, ",ef" if self.uses_ef else "")


def wire_roundtrip(rows, dtype=None, codec=None):
    """THE precision-loss semantics of rows crossing the wire, in one
    place: forged rows are squeezed through the exchange exactly like
    honest ones (an omniscient attacker's matrix still ships as encoded
    bytes).  ``dtype`` is the engine's ``exchange_dtype``, ``codec``
    its codec; both None is the f32 wire (identity)."""
    import jax.numpy as jnp

    if codec is not None:
        return codec.roundtrip_rows(rows) if rows.ndim > 1 else codec.roundtrip(rows)
    if dtype is not None:
        return rows.astype(dtype).astype(jnp.float32)
    return rows


def bytes_per_row(d, dtype=None, codec=None):
    """Wire bytes of one (d,) submission row under the configured
    exchange — the accounting behind ``bytes_on_wire_total``."""
    if codec is not None:
        return int(codec.bytes_per_row(d))
    if dtype is not None:
        return int(d) * int(np.dtype(dtype).itemsize)
    return int(d) * _F32_BYTES


def compression_ratio(d, dtype=None, codec=None):
    """Bytes-on-wire ratio vs the f32 exchange (>= 1)."""
    return (int(d) * _F32_BYTES) / float(bytes_per_row(d, dtype=dtype, codec=codec))


def describe(dtype=None, codec=None):
    """The exchange spec string for telemetry/summary labels."""
    if codec is not None:
        return codec.spec()
    if dtype is not None:
        return str(np.dtype(dtype).name)
    return "float32"
