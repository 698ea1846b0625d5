"""Fault injection: deterministic crash / hang / SIGTERM / SDC at a step.

The recovery path deserves the same adversarial testing the detection
path got (PR 7's simulated hangs and injected stragglers): this harness
injects the failure shapes the resilience layer exists for, at an exact
step boundary, identically from unit tests, the 2-process emulated world
and ``main.py --chaos``.

Spec grammar (``ChaosSpec.parse``; ``parse_chaos`` accepts a
comma-separated list so one drill can compose, e.g., an SDC with a later
spike — ``"bitflip@10,nanburst:3@20"``)::

    <kind>[:<n>]@<step>[@<generation>]

    crash@12        raise ChaosCrash after step 12 completes (gen 0 only)
    sigterm@12      SIGTERM self after step 12 (the preemption drill)
    hang:600@12     block the loop 600 s after step 12 (watchdog food)
    corrupt@12      truncate the NEWEST checkpoint's files after step 12,
                    then crash — the die-mid-write drill that the
                    corrupt-checkpoint fallback (``Checkpointer.restore``
                    walking back to the previous step) must absorb
    bitflip@12      flip ONE low mantissa bit of one element of one
                    data-replica's copy of a replicated param leaf after
                    step 12 — the silent-data-corruption signature the
                    replica-divergence probe (and the repair loop riding
                    it) exists to catch; training continues numerically
                    almost unchanged, which is exactly the danger
    nanburst:3@12   poison the input batches of steps 13..15 with NaNs —
                    THREE consecutive non-finite steps, defeating the
                    single-step ``guard_nonfinite`` skip (the repair
                    loop's skip-streak trigger); ``:n`` defaults to 1
    crash@5@*       crash at step 5 in EVERY generation — the
                    deterministic-crash loop that must exhaust the
                    supervisor's restart budget, not spin

The generation field defaults to ``0``: an injected incident happens once,
in the first life of the job, and the relaunched generation — which
resumes AT the trigger step — must not re-fire it. ``*`` fires in every
generation (deterministic bugs don't go away on restart) — and, for the
repair drills, :meth:`ChaosInjector.rearm` re-arms ``@*`` specs after an
in-process repair too, because a deterministic bug doesn't go away on a
rollback either. ``fit()`` calls :meth:`ChaosInjector.maybe_fire` (and
:meth:`maybe_flip` for ``bitflip``) with the number of COMPLETED steps at
each loop boundary, before dispatching the next step — so ``sigterm@k``
yields an emergency checkpoint at exactly step ``k`` and a resume at
``k+1``; ``nanburst`` rides :meth:`wrap_batches` around the input stream
instead (it poisons data, not a boundary).
"""

from __future__ import annotations

import dataclasses
import os
import signal
import sys
import time

from tpudist.resilience.exitcodes import restart_generation

__all__ = ["ChaosCrash", "ChaosSpec", "ChaosInjector", "make_injector",
           "parse_chaos", "corrupt_latest_checkpoint", "flip_param_bit"]

KINDS = ("crash", "hang", "sigterm", "corrupt", "bitflip", "nanburst")
#: kinds that fire at a step boundary through maybe_fire (bitflip has its
#: own state-mutating hook, nanburst wraps the input stream)
BOUNDARY_KINDS = ("crash", "hang", "sigterm", "corrupt")
DEFAULT_HANG_S = 3600.0


class ChaosCrash(RuntimeError):
    """The injected deterministic crash — a real exception through the
    real crash path (fit's handler, the run report's ``crashed:`` status,
    the launcher's non-restartable exit)."""


@dataclasses.dataclass(frozen=True)
class ChaosSpec:
    kind: str
    step: int
    duration_s: float = DEFAULT_HANG_S
    generation: int | None = 0  # None = every generation ("*")
    count: int = 1  # nanburst only: consecutive poisoned steps

    @classmethod
    def parse(cls, spec: str) -> "ChaosSpec":
        parts = str(spec).strip().split("@")
        if len(parts) not in (2, 3):
            raise ValueError(
                f"chaos spec {spec!r} is not '<kind>[:<n>]@<step>"
                f"[@<generation>|@*]'"
            )
        head, step_s = parts[0], parts[1]
        kind, _, dur = head.partition(":")
        if kind not in KINDS:
            raise ValueError(
                f"chaos kind {kind!r} not in {KINDS} (spec {spec!r})"
            )
        duration = float(dur) if dur and kind == "hang" else DEFAULT_HANG_S
        count = 1
        if kind == "nanburst" and dur:
            count = int(dur)
            if count < 1:
                raise ValueError(
                    f"nanburst count must be >= 1 (spec {spec!r})"
                )
        if dur and kind not in ("hang", "nanburst"):
            raise ValueError(
                f"only 'hang' (seconds) and 'nanburst' (step count) take "
                f"a ':<n>' field (spec {spec!r})"
            )
        gen: int | None = 0
        if len(parts) == 3:
            gen = None if parts[2] == "*" else int(parts[2])
        return cls(kind=kind, step=int(step_s), duration_s=duration,
                   generation=gen, count=count)


def parse_chaos(spec: str) -> list[ChaosSpec]:
    """One ``--chaos`` string → specs. Single-spec strings parse exactly
    as before (byte-compatible grammar); commas compose several injections
    into one drill."""
    out = [ChaosSpec.parse(p) for p in str(spec).split(",") if p.strip()]
    if not out:
        raise ValueError(f"chaos spec {spec!r} names no injection")
    return out


class ChaosInjector:
    """One-shot triggers bound to this process's restart generation.

    Accepts one spec or a list (``parse_chaos``); each spec fires at most
    once per arming (``rearm`` re-arms the ``@*`` deterministic-bug specs
    after an in-process repair). ``spec`` keeps the single-spec view for
    the common case; ``fired`` is True once every spec has fired.
    """

    def __init__(self, spec, *, generation: int | None = None,
                 sleep=time.sleep, kill=os.kill):
        specs = [spec] if isinstance(spec, ChaosSpec) else list(spec)
        if not specs:
            raise ValueError("ChaosInjector needs at least one spec")
        self.specs: list[ChaosSpec] = specs
        self.spec = specs[0]
        self._fired = [False] * len(specs)
        self.generation = (
            restart_generation() if generation is None else int(generation)
        )
        self._sleep = sleep
        self._kill = kill
        # the corrupt drill's target; fit() binds its checkpoint_dir
        self.checkpoint_dir = None
        self._wait = None

    @property
    def fired(self) -> bool:
        return all(self._fired)

    def _armed(self, sp: ChaosSpec) -> bool:
        return sp.generation is None or self.generation == sp.generation

    def rearm(self) -> None:
        """Re-arm the ``@*`` (every-generation) specs — called by fit()'s
        repair handler: a deterministic bug doesn't go away on a rollback
        any more than on a restart, so the drill must keep biting until
        the repair budget circuit-breaks. Generation-pinned specs stay
        one-shot (a transient incident repaired is an incident gone)."""
        for i, sp in enumerate(self.specs):
            if sp.generation is None:
                self._fired[i] = False

    def bind(self, checkpoint_dir, wait=None) -> "ChaosInjector":
        """Attach the run's checkpoint dir (the ``corrupt`` kind's
        target) and optionally the checkpointer's ``wait`` (so the drill
        corrupts a DETERMINISTIC step: the newest save is made durable
        before the truncation, instead of racing the async commit);
        chained so ``make_injector(...).bind(dir)`` reads naturally.
        No-op for the other kinds."""
        self.checkpoint_dir = checkpoint_dir
        self._wait = wait
        return self

    def maybe_fire(self, completed_step: int) -> bool:
        """Fire due boundary-kind specs once ``completed_step`` reaches
        their step in an armed generation. Returns True if any fired
        (crash/corrupt raise instead)."""
        fired_any = False
        for i, sp in enumerate(self.specs):
            if sp.kind not in BOUNDARY_KINDS:
                continue
            if (self._fired[i] or completed_step < sp.step
                    or not self._armed(sp)):
                continue
            self._fired[i] = True
            fired_any = True
            if sp.kind == "crash":
                raise ChaosCrash(
                    f"chaos: injected crash after step {completed_step} "
                    f"(generation {self.generation})"
                )
            if sp.kind == "hang":
                self._sleep(sp.duration_s)
                continue
            if sp.kind == "corrupt":
                if self._wait is not None:
                    # settle async saves: corrupt a committed step
                    self._wait()
                corrupt_latest_checkpoint(self.checkpoint_dir)
                # then die the way a real mid-write preemption does: a
                # hard crash, so the supervisor's relaunch exercises the
                # fallback walk end to end
                raise ChaosCrash(
                    f"chaos: corrupted newest checkpoint after step "
                    f"{completed_step} (generation {self.generation})"
                )
            # sigterm: the preemption drill — the signal lands on this
            # very process; with fit()'s PreemptionGuard installed the
            # flag is set before the next step dispatches
            self._kill(os.getpid(), signal.SIGTERM)
        return fired_any

    def maybe_flip(self, completed_step: int, state, mesh=None):
        """The ``bitflip`` drill: at its step boundary, return ``state``
        with one mantissa bit flipped in ONE data-replica's copy of a
        replicated param leaf (:func:`flip_param_bit`). No-op (state
        returned unchanged) for other kinds / unarmed generations."""
        for i, sp in enumerate(self.specs):
            if sp.kind != "bitflip":
                continue
            if (self._fired[i] or completed_step < sp.step
                    or not self._armed(sp)):
                continue
            self._fired[i] = True
            state, info = flip_param_bit(state, mesh=mesh)
            print(
                f"chaos: bitflip after step {completed_step} — {info}",
                file=sys.stderr, flush=True,
            )
        return state

    def wrap_batches(self, batches, first_step: int):
        """The ``nanburst`` drill: wrap an epoch's batch iterator so the
        batches feeding steps ``(spec.step, spec.step + count]`` carry a
        NaN in their first float leaf — ``count`` CONSECUTIVE non-finite
        steps, which a single-step ``guard_nonfinite`` skip absorbs one
        at a time but never escapes (the repair loop's skip-streak
        trigger exists for exactly this shape). ``first_step`` is the
        step the iterator's first batch will train (fit passes
        ``global_step + 1`` when it builds each epoch's stream; prefetch
        consuming ahead is fine — the mapping is positional)."""
        bursts = [
            i for i, sp in enumerate(self.specs)
            if sp.kind == "nanburst" and self._armed(sp)
        ]
        if not bursts:
            return batches

        def _gen():
            for j, batch in enumerate(batches):
                s = first_step + j  # the step this batch trains
                for i in bursts:
                    sp = self.specs[i]
                    if sp.step < s <= sp.step + sp.count:
                        self._fired[i] = True
                        batch = _poison_batch(batch, s)
                yield batch

        return _gen()


def _poison_batch(batch, step: int):
    """One NaN in the first float leaf — enough to make the loss (and the
    whole backward) non-finite. Copies the poisoned leaf only."""
    import numpy as np

    out = dict(batch)
    for k, v in batch.items():
        if k.startswith("_"):
            continue
        arr = np.asarray(v)
        if np.issubdtype(arr.dtype, np.floating):
            arr = np.array(arr, copy=True)
            arr.reshape(-1)[:1] = np.nan
            out[k] = arr
            return out
    raise ChaosCrash(
        f"chaos: nanburst at step {step} found no float batch leaf to "
        "poison (integer-token batches have no NaN representation — "
        "drill spikes on a float-input model, or use bitflip for SDCs)"
    )


def flip_param_bit(state, mesh=None, *, bit: int = 0):
    """Flip one mantissa bit of element 0 of ONE data-replica's copy of
    the first replicated float param leaf — the SDC signature: every
    replica still *claims* the same (replicated) array, but one device's
    buffer now disagrees by a single bit, which only the bit-exact
    replica-divergence probe (``tpudist.parallel.dp
    .make_divergence_probe``) can see. Returns ``(new_state, info)``.

    The corrupted replica is the LAST device of the mesh (or of the
    leaf's device set) — never replica 0, which the probe compares
    against. Raises :class:`ChaosCrash` when no data-replicated float
    leaf exists (a fully TP/FSDP-sharded state has no redundant copy to
    corrupt — the drill would be meaningless).
    """
    import numpy as np

    import jax
    import jax.numpy as jnp
    import jax.tree_util as jtu

    leaves = jtu.tree_flatten_with_path(state.params)[0]
    target_leaf = None
    elt = 0
    for path, leaf in leaves:
        if not isinstance(leaf, jax.Array):
            continue
        if not jnp.issubdtype(leaf.dtype, jnp.floating) or leaf.size < 1:
            continue
        if not leaf.sharding.is_fully_replicated:
            continue
        if target_leaf is None:
            target_leaf = (path, leaf)
        # prefer a NONZERO element: flipping a mantissa bit of 0.0 makes
        # a denormal (~1e-45) that the next optimizer add absorbs by
        # rounding — the "SDC" would silently self-heal before any probe
        # cadence, which is not how a flipped weight bit behaves
        nz = np.flatnonzero(np.asarray(leaf.addressable_shards[0].data))
        if nz.size:
            target_leaf = (path, leaf)
            elt = int(nz[0])
            break
    if target_leaf is None:
        raise ChaosCrash(
            "chaos: bitflip found no fully-replicated float param leaf — "
            "nothing redundant to corrupt (TP/FSDP-sharded states keep "
            "one copy; use nanburst or corrupt instead)"
        )
    path, leaf = target_leaf
    if mesh is not None:
        target_dev = mesh.devices.flat[-1]
    else:
        target_dev = sorted(leaf.sharding.device_set, key=lambda d: d.id)[-1]
    itemsize = np.dtype(leaf.dtype).itemsize
    uview = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}[itemsize]
    bufs, flipped = [], False
    for sh in leaf.addressable_shards:
        data = np.array(sh.data)  # a full copy: the leaf is replicated
        if sh.device == target_dev:
            u = data.view(uview)
            u.reshape(-1)[elt] ^= np.asarray(1 << bit, uview)
            flipped = True
        bufs.append(jax.device_put(data, sh.device))
    new_leaf = jax.make_array_from_single_device_arrays(
        leaf.shape, leaf.sharding, bufs
    )
    flat, treedef = jtu.tree_flatten(state.params)
    for i, old in enumerate(flat):
        if old is leaf:
            flat[i] = new_leaf
            break
    info = {
        "leaf": "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                         for k in path),
        "device": str(target_dev),
        "element": int(elt),
        "bit": int(bit),
        # multi-process: only the process owning target_dev flips; the
        # others rebuild identical buffers (the flip is still global —
        # the array IS that device's buffer on that device)
        "flipped_locally": bool(flipped),
    }
    return state.replace(params=jtu.tree_unflatten(treedef, flat)), info


def corrupt_latest_checkpoint(checkpoint_dir) -> int:
    """Truncate every file of the NEWEST step dir under
    ``checkpoint_dir`` to half its size — the torn state a preemption
    landing mid-checkpoint-write leaves behind. The dir itself survives
    (so ``latest_step`` still points at it: exactly the poisoned-resume
    scenario the fallback walk exists for). Returns the corrupted step."""
    from pathlib import Path

    from tpudist.checkpoint import latest_step

    if checkpoint_dir is None:
        raise ChaosCrash(
            "chaos: corrupt@step needs a checkpoint_dir (fit binds it; "
            "standalone injectors use .bind(dir))"
        )
    step = latest_step(checkpoint_dir)
    if step is None:
        raise ChaosCrash(
            f"chaos: corrupt@step found no checkpoint under "
            f"{checkpoint_dir} to corrupt — schedule it after the first "
            "save (checkpoint_every)"
        )
    step_dir = Path(checkpoint_dir) / str(step)
    for f in sorted(p for p in step_dir.rglob("*") if p.is_file()):
        size = f.stat().st_size
        with open(f, "r+b") as fh:
            fh.truncate(size // 2)
    return step


def make_injector(chaos) -> ChaosInjector | None:
    """``fit()``'s coercion point: None | spec string (single or
    comma-separated) | ChaosSpec | list of ChaosSpecs | ready-made
    ChaosInjector."""
    if chaos is None:
        return None
    if isinstance(chaos, ChaosInjector):
        return chaos
    if isinstance(chaos, ChaosSpec):
        return ChaosInjector(chaos)
    if isinstance(chaos, (list, tuple)):
        return ChaosInjector(list(chaos))
    return ChaosInjector(parse_chaos(chaos))
