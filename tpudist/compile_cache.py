"""AOT executable cache: a restarted world should not re-pay the trace.

PR 8's goodput accounting prices every restart as bringup + restore +
compile, and compile is the dominant recurring term (the reason the chaos
drill's recovery bound is set in minutes, not seconds): every relaunch of
the SAME program on the SAME hardware re-traces and re-compiles the train
step from scratch. XLA executables are serializable
(``jax.experimental.serialize_executable`` — the AOT-lowering workflow of
the TPUv4 pjit experience reports, PAPERS.md), so generation N can leave
its compiled step on disk and generation N+1 can load it while the
checkpoint restore is still streaming — tracing skipped entirely.

The cache is CONTENT-KEYED (:func:`step_key`): a SHA-256 over

- the device topology (platform/kind per device, process count, mesh
  axis names and sizes) — an executable is placement-specific;
- the program geometry: every train-state and staged-batch leaf's path,
  shape, dtype, and partition spec;
- the step configuration (``make_train_step``'s knobs: reduce method,
  fused set, telemetry/guard, grad_accum, remat, loss/model identity);
- the jax/jaxlib versions (an executable is not portable across them).

Anything the key cannot see but that changes GEOMETRY (a foreign loader
whose batches disagree with its probe, a topology the key hashed
differently) is handled by the contract, not the hash: the cached
executable is validated on first call and any input mismatch falls
through to the ordinary jit path with a telemetry ``warning``. The key
folds the model/loss IDENTITY (type + repr / qualname) precisely so
config-level changes move it — but a pure CODE edit with identical
geometry and identical identity (editing a loss function's body, or a
model whose repr doesn't expose the changed knob) is invisible to both
the key and the call-time check: bump the cache directory (or
``step_key``'s ``salt``) after such edits. When the model's repr is the
default address-bearing one the key degrades to type-only and ``fit``
emits a ``compile_cache_weak_key`` warning row saying exactly this. ``fit(compile_cache=dir)`` wires it up
(overlapping the deserialization with checkpoint restore) and the
one-shot ``compile_cache`` telemetry row records hit/miss/bytes/load_s
(docs/OBSERVABILITY.md); ``tpudist.resilience.goodput`` attributes a warm
first iteration to ``cache_load_s`` instead of mislabeling it
``compile_s``.

The serving engine reuses this store for its program inventory
(``ServeEngine(compile_cache=dir)``, docs/SERVING.md §5) with its own
fingerprint discipline: the engine's key covers the model identity,
params geometry, every scheduler knob — and, on a tensor-sharded engine
(``mesh=``, docs/SERVING.md §7), the mesh axis names/shape and the
tensor world, for the same reason ``step_key`` hashes the topology: an
executable lowered with committed ``NamedSharding`` arguments is
placement-specific, and a single-chip artifact must never warm-start a
sharded engine (or vice versa, or across different tensor worlds).
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import re
import tempfile
import threading
import time
from pathlib import Path
from typing import Any, Callable

import jax
import numpy as np

__all__ = ["CompileCache", "model_identity", "step_key", "staged_example",
           "wrap_step"]

#: bump to invalidate every existing cache entry on a format change
SCHEMA = 1


def _leaf_rows(tree) -> list[list]:
    import jax.tree_util as jtu

    rows = []
    for path, leaf in jtu.tree_flatten_with_path(tree)[0]:
        spec = getattr(getattr(leaf, "sharding", None), "spec", None)
        rows.append([
            jtu.keystr(path),
            list(getattr(leaf, "shape", ())),
            str(getattr(leaf, "dtype", type(leaf).__name__)),
            str(spec),
        ])
    return rows


def step_key(*, mesh, state, batch, config: dict, salt: str = "") -> str:
    """Content hash identifying one compiled train step on one topology.
    ``state``/``batch`` contribute shapes/dtypes/shardings only (values
    never matter to the executable); ``config`` is the step-builder's knob
    dict; ``salt`` lets a caller segregate entries it knows the key can't
    distinguish (e.g. two custom ``forward_loss`` closures with identical
    geometry)."""
    devices = [
        [d.platform, getattr(d, "device_kind", ""), int(d.process_index)]
        for d in mesh.devices.flat
    ]
    doc = {
        "schema": SCHEMA,
        "jax": jax.__version__,
        "jaxlib": getattr(
            __import__("jaxlib"), "__version__", "?"
        ),
        "topology": {
            "devices": devices,
            "process_count": int(jax.process_count()),
            "mesh_axes": list(mesh.axis_names),
            "mesh_shape": [int(mesh.shape[a]) for a in mesh.axis_names],
        },
        "state": _leaf_rows(state),
        "batch": _leaf_rows(batch),
        "config": {k: config[k] for k in sorted(config)},
        "salt": salt,
    }
    blob = json.dumps(doc, sort_keys=True, default=str).encode("utf-8")
    return hashlib.sha256(blob).hexdigest()[:32]


def model_identity(model) -> str:
    """A process-stable identity for the model in the cache key: type
    qualname plus its repr — UNLESS the repr is the default
    address-bearing ``<X object at 0x...>``, which differs in every
    relaunched generation and would turn every lookup into a silent miss
    (unbounded orphan entries, the feature defeated with no warning).
    Flax modules and dataclasses print their config stably; anything
    else contributes its type only (callers who need finer distinction
    have ``step_key``'s ``salt``)."""
    ident = f"{type(model).__module__}.{type(model).__qualname__}"
    r = repr(model)
    if re.search(r" at 0x[0-9a-fA-F]+", r):
        return ident
    return f"{ident}:{r}"


def staged_example(step, loader):
    """A zeros-filled staged batch with exactly the shapes/shardings the
    real training batches will have (``step.stage`` applies the whole
    staging contract, grad-accumulation folding included) — what
    :meth:`CompileCache` keys and lowers against. ``None`` when the
    loader cannot be probed or stages device-resident operands (``"_"``
    keys ride outside the host batch and are not reconstructable from
    shapes) — the caller then skips the cache rather than guessing."""
    try:
        if hasattr(loader, "probe"):
            sample = loader.probe()
        else:
            it = iter(loader)
            if it is loader:
                # a single-shot iterator: pulling a sample here would
                # silently EAT the first training batch — decline the
                # cache instead of corrupting the data order
                return None
            sample = next(it)
        rows = int(loader.batch_size)
    except Exception:
        return None
    if any(str(k).startswith("_") for k in sample):
        return None
    if callable(getattr(loader, "input_transform", None)):
        # the device-cache loader family (tpudist.data.device_cache):
        # every REAL batch carries the HBM cache as a "_cache" operand,
        # but the probe deliberately describes the post-gather image row
        # (fit's init contract) — keying/lowering from it would fail on
        # the first real batch every generation. The in-graph-gather
        # contract IS the input_transform method; decline cleanly.
        return None
    fake = {
        k: np.zeros((rows,) + tuple(np.asarray(v).shape[1:]),
                    np.asarray(v).dtype)
        for k, v in sample.items()
    }
    try:
        return step.stage(fake)
    except Exception:
        return None


class _LoadHandle:
    """An in-flight background deserialization — started BEFORE the
    checkpoint restore so the two overlap; ``result()`` joins."""

    def __init__(self, fn: Callable[[], Any]):
        self.value = None
        self.error: Exception | None = None
        self.seconds = 0.0

        def run():
            t0 = time.perf_counter()
            try:
                self.value = fn()
            except Exception as exc:  # any failure = miss
                self.error = exc
            self.seconds = time.perf_counter() - t0

        self._thread = threading.Thread(
            target=run, name="tpudist-compile-cache-load", daemon=True
        )
        self._thread.start()

    def result(self):
        self._thread.join()
        return self.value


class CompileCache:
    """A directory of serialized step executables, one file per key
    (``<key>.aot`` payload + ``<key>.json`` human-readable sidecar).
    Every operation is fail-soft: a corrupt/alien/mismatched entry is a
    miss, a failed store is a warning — the cache may only ever cost
    time, never correctness."""

    def __init__(self, directory: str | Path):
        self.directory = Path(directory)
        self.directory.mkdir(parents=True, exist_ok=True)
        self.last_load_error: str | None = None

    def path_for(self, key: str) -> Path:
        return self.directory / f"{key}.aot"

    # -- load --------------------------------------------------------------

    def load(self, key: str):
        """Deserialize the executable stored under ``key`` or return
        ``None`` (miss/corrupt/version-mismatch — all fail-soft; the
        failure, if any, lands in ``last_load_error``)."""
        from jax.experimental import serialize_executable

        self.last_load_error = None
        p = self.path_for(key)
        if not p.exists():
            return None
        try:
            blob = pickle.loads(p.read_bytes())
            if blob.get("schema") != SCHEMA:
                return None
            return serialize_executable.deserialize_and_load(
                blob["payload"], blob["in_tree"], blob["out_tree"]
            )
        except Exception as exc:
            self.last_load_error = f"{type(exc).__name__}: {exc}"
            return None

    def begin_load(self, key: str) -> _LoadHandle:
        """Start the deserialization on a side thread — fit() calls this
        before the checkpoint restore so the two IO-and-deserialize legs
        overlap instead of serializing."""
        return _LoadHandle(lambda: self.load(key))

    # -- store -------------------------------------------------------------

    def store(self, key: str, compiled, meta: dict | None = None) -> int:
        """Serialize ``compiled`` under ``key`` (atomic tmp+replace, one
        writer wins). Returns the payload size in bytes, 0 on any
        failure. Rank 0 only — serialization of a large step is real CPU
        and memory, and N-1 ranks would discard the blob (the telemetry
        row that reports the byte count is rank-0-only too)."""
        from jax.experimental import serialize_executable

        if jax.process_index() != 0:
            return 0
        try:
            payload, in_tree, out_tree = serialize_executable.serialize(
                compiled
            )
            blob = pickle.dumps({
                "schema": SCHEMA,
                "payload": payload,
                "in_tree": in_tree,
                "out_tree": out_tree,
            })
            fd, tmp = tempfile.mkstemp(
                dir=self.directory, prefix=f".{key}.", suffix=".tmp"
            )
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
            os.replace(tmp, self.path_for(key))
            self.path_for(key).with_suffix(".json").write_text(
                json.dumps({
                    "key": key,
                    "bytes": len(blob),
                    "jax": jax.__version__,
                    "created": time.time(),
                    **(meta or {}),
                })
            )
            return len(blob)
        except Exception:
            return 0

    # -- the whole bring-up path ------------------------------------------

    def finish(self, handle: _LoadHandle | None, step, state, staged,
               key: str, meta: dict | None = None):
        """Join the background load; on a miss, AOT-compile the step NOW
        (bring-up, where goodput attributes it honestly) and store it.
        Returns ``(executable_or_None, info)`` where ``info`` is the
        telemetry ``compile_cache`` row's payload."""
        info: dict[str, Any] = {"key": key, "hit": False, "bytes": 0,
                                "load_s": 0.0, "load_wait_s": 0.0,
                                "compile_s": 0.0, "store_s": 0.0}
        t_join = time.perf_counter()
        exe = handle.result() if handle is not None else None
        if handle is not None:
            # load_s: the deserialization's own duration (what the cache
            # actually cost in CPU terms); load_wait_s: how long THIS
            # thread blocked joining it — the part NOT hidden behind the
            # overlapped checkpoint restore, i.e. the load's contribution
            # to wall time. Goodput books the wait (its partition must
            # stay disjoint from restore_s); the telemetry row carries
            # both. The wait clamps to the load itself: an immediate join
            # also measures thread-startup/epilogue lag the load never
            # contained, and "wait <= load" is the row's invariant.
            info["load_s"] = round(handle.seconds, 6)
            info["load_wait_s"] = round(
                min(time.perf_counter() - t_join, handle.seconds), 6
            )
            if handle.error is not None:
                info["error"] = (
                    f"{type(handle.error).__name__}: {handle.error}"
                )
            elif self.last_load_error is not None:
                info["error"] = self.last_load_error
        if exe is not None:
            info["hit"] = True
            try:
                info["bytes"] = self.path_for(key).stat().st_size
            except OSError:
                pass
            return exe, info
        try:
            t0 = time.perf_counter()
            compiled = step.jitted.lower(state, staged).compile()
            info["compile_s"] = round(time.perf_counter() - t0, 6)
            t0 = time.perf_counter()
            info["bytes"] = self.store(key, compiled, meta)
            info["store_s"] = round(time.perf_counter() - t0, 6)
            return compiled, info
        except Exception as exc:
            # lowering/compiling outside the jit fast path failed (exotic
            # step configuration): fall through to ordinary tracing
            # the full message: a compiler refusal on the chip must be
            # readable in the compile_cache row, not cut to its preamble
            info["error"] = f"{type(exc).__name__}: {exc}"
            return None, info


def wrap_step(step, executable, on_fallback: Callable | None = None,
              expected_batch=None):
    """The AOT-warmed step: same calling convention and attributes as
    ``make_train_step``'s product, but dispatching through ``executable``
    (cache-loaded or freshly AOT-compiled). The FIRST call validates it —
    an input mismatch (a geometry the content key could not distinguish)
    raises before execution, and the wrapper permanently falls back to
    the ordinary ``step.jitted`` path, reporting through ``on_fallback``;
    after one successful call the executable is trusted for that
    geometry. ``expected_batch`` (the staged example the executable was
    keyed/compiled against) additionally routes any OFF-SHAPE batch —
    e.g. a ``drop_remainder=False`` loader's ragged tail, which the jit
    path absorbs by recompiling — to ``step.jitted`` per call instead of
    letting a post-validation shape mismatch kill the run."""
    holder = {"exe": executable, "validated": False, "noted_cold": False}
    expected = None
    if expected_batch is not None:
        expected = {
            k: (tuple(v.shape), v.dtype) for k, v in expected_batch.items()
        }

    def _on_shape(staged) -> bool:
        if expected is None:
            return True
        return set(staged) == set(expected) and all(
            (tuple(v.shape), v.dtype) == expected[k]
            for k, v in staged.items()
        )

    def cached(state, batch):
        staged = step.stage(batch)
        exe = holder["exe"]
        if exe is None or not _on_shape(staged):
            if (exe is not None and not holder["validated"]
                    and not holder["noted_cold"]):
                # the FIRST call is already off-shape (e.g. every batch
                # ragged because the dataset is smaller than batch_size,
                # or a loader whose batch_size attribute lied): this
                # iteration traces on the jit path — report it so
                # goodput reverts its warm-start accounting instead of
                # booking a real cold compile as productive time. The
                # executable stays: later on-shape batches may use it.
                holder["noted_cold"] = True
                if on_fallback is not None:
                    on_fallback(RuntimeError(
                        "first batch off-shape vs the staged example — "
                        "iteration 1 traces on the jit path"
                    ))
            return step.jitted(state, staged)
        if holder["validated"]:
            return exe(state, staged)
        try:
            out = exe(state, staged)
        except Exception as exc:
            holder["exe"] = None
            if on_fallback is not None:
                on_fallback(exc)
            return step.jitted(state, staged)
        holder["validated"] = True
        return out

    for attr in ("jitted", "stage", "grad_reducer", "comm_stats",
                 "fused", "fused_info"):
        setattr(cached, attr, getattr(step, attr))
    cached.aot = holder
    return cached
