"""Unified solver-loop runtime: masked iteration + early-exit compaction.

Both paper solvers share one outer orchestration — a per-instance-masked
while-loop over "heuristic cycles" (a fixed number of Jacobi rounds plus a
vectorized heuristic pass).  A solver registers the pieces as a ``LoopSpec``:

* ``cycle(state) -> state`` — one heuristic cycle, batch-polymorphic and
  PER-INSTANCE PURE: instance ``b`` of the output depends only on instance
  ``b`` of the input (every reduction runs over trailing data axes; shared
  while-loop predicates inside, like a BFS fixpoint's ``changed``, may add
  no-op iterations but never change an instance's values),
* ``live(state, rounds) -> (...,) bool`` — the per-instance liveness mask,
* ``rounds_per_cycle`` — the per-instance round-accounting increment,
* ``lead_axes_fn(leaf, batch_ndim) -> int`` — how many leaf axes PRECEDE
  the batch axes (the freeze/gather/scatter spec; ``None`` = batch leads
  every leaf).

and the runtime owns the iteration in one of two modes:

* ``run_masked`` — the jittable baseline: every cycle computes the whole
  batch and ``freeze`` selects the old state back in for non-live
  instances.  A converged instance is an exact no-op — but still pays full
  FLOPs every cycle until the whole batch finishes.
* ``run_compacted`` — early-exit compaction (the ROADMAP item; cf. the
  active-set compaction of workload-balanced GPU push-relabel): a
  host-driven loop gathers still-live instances into dense pow2-sized
  sub-batches (fixed bucket sizes bound recompiles to <= log2(B) + 2 per
  solver config), runs the SAME jitted cycle on the compacted sub-batch,
  and scatters results back in input order.  Converged instances stop
  consuming FLOPs entirely instead of being select-masked forever.

Because cycles are per-instance pure, both modes execute the identical
per-instance trajectory: compacted results bit-match masked results, which
bit-match a loop of single-instance solves (tests/test_compact.py).

Sharding: ``run_compacted`` accepts per-shard LANES — contiguous batch
slices pinned to devices (``repro.launch.mesh.compact_lanes``).  Compaction
then happens within each lane only: instances never migrate between shards
and no collectives are introduced, preserving the shard-independence
contract of the mesh path.  Lane dispatches are issued before any liveness
mask is fetched, so devices run their cycles concurrently.

Continuous batching: ``run_compacted`` additionally accepts a REFILL hook
(``refill=``) — the cycle boundary where the host already re-gathers the
live set is also where a caller may inject NEW instances into slots
vacated by converged ones, instead of letting freed slots idle until the
whole batch drains (the admit-each-step structure of continuous-batching
LLM servers, applied to round-synchronous solvers).  Because admitted
instances enter with a fresh rounds counter and the cycles are
per-instance pure, a refilled run executes every instance's exact
solo-solve trajectory: values AND counters bit-match a loop of single
solves (tests/test_refill.py).  ``repro.core.refill`` wraps the hook
protocol into a per-kind session object.

Drivers: a kind hands its instance operations (``KindOps``: the spec plus
jitted init / finalize / fused masked solve, pad-one, shape-of and crop)
to ``solve``, the one driver ladder — compacted, sharded
(``repro.launch.mesh.dispatch_sharded``), host-stepped for cycle
telemetry, or the fused masked program.  ``drive`` runs an already built
state (warm starts, refill sessions) through the same drivers.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.masking import freeze

class CycleEvent(NamedTuple):
    """One structured per-cycle telemetry sample (``cycle_events``).

    Emitted BEFORE each cycle dispatches, by both drivers:

    * ``driver`` — ``"masked"`` or ``"compacted"``.
    * ``cycle`` — host cycle index, from 0.
    * ``n_live`` — still-live instances entering this cycle (all lanes).
    * ``rounds_total`` — sum of the per-slot rounds counters so far (with
      refill, counters describe current slot OCCUPANTS — admissions reset
      their slot, so treat this as a diagnostic, not a monotone total).
    * ``gathered`` — instances this cycle will actually compute: the
      padded pow2 sub-batch total for the compacted driver, the full
      batch size for the masked driver (its converged instances still pay
      FLOPs — exactly the waste ``gathered - n_live`` measures).
    * ``heur_total`` — sum of per-instance heuristic-invocation counters
      over the live set (the balanced backend's ``heuristics``), or
      ``None`` when the spec registers no ``heur`` extractor or the hook
      was installed without ``detail=True`` (fetching counters costs a
      device read per cycle, so it is opt-in).
    """

    driver: str
    cycle: int
    n_live: int
    rounds_total: int
    gathered: int
    heur_total: int | None


class _CycleHook(NamedTuple):
    fn: Callable          # CycleEvent -> None
    masked: bool          # also host-step run_masked to observe its cycles
    detail: bool          # fetch heur counters per cycle (a device read)


# Per-cycle telemetry hook. ``run_compacted`` fetches the live set every
# host cycle anyway, so emitting is nearly free there; ``run_masked`` is a
# jitted while_loop whose liveness never reaches the host — it emits only
# for hooks installed with ``masked=True``, by HOST-STEPPING the same
# jitted cycle (see ``run_masked``). Thread-local (a ContextVar) on
# purpose: the serving scheduler's lane threads trace their own
# dispatches without seeing each other's cycles — and the disabled cost
# is one contextvar read per solve.
_cycle_hook: contextvars.ContextVar["_CycleHook | None"] = \
    contextvars.ContextVar("solver_loop_cycle_hook", default=None)


@contextlib.contextmanager
def cycle_events(fn: Callable, *, masked: bool = False,
                 detail: bool = False):
    """Install ``fn(event: CycleEvent)`` as this thread's cycle hook.

    While active, every host cycle of ``run_compacted`` emits one
    ``CycleEvent`` (all lanes aggregated) BEFORE dispatching that cycle.
    With ``masked=True``, eager ``run_masked`` solves emit too: the
    driver host-steps its jitted cycle instead of lowering one fused
    while_loop — bit-identical results (the same per-cycle jit the
    compacted driver uses), at the cost of a host sync per cycle, so the
    serving scheduler's always-on metrics hook leaves it off.  With
    ``detail=True``, events include ``heur_total`` for specs that
    register a ``heur`` extractor (one extra device fetch per cycle).

    The hook must be cheap and must not raise.
    """
    token = _cycle_hook.set(_CycleHook(fn, masked, detail))
    try:
        yield
    finally:
        _cycle_hook.reset(token)


@contextlib.contextmanager
def trace_cycles(fn: Callable[[int, int], None]):
    """Back-compat shim over ``cycle_events``: ``fn(cycle_index, n_live)``.

    The original compaction-trace hook (``repro.serve.metrics`` records
    live-set decay through it). Equivalent to ``cycle_events`` with an
    adapter that drops every field but ``cycle`` and ``n_live``; masked
    solves do not emit (the pre-``CycleEvent`` behaviour).
    """
    with cycle_events(lambda ev: fn(ev.cycle, ev.n_live)):
        yield


def masked_events_active() -> bool:
    """Is a ``cycle_events(masked=True)`` hook installed on this thread?

    ``solve`` and ``drive`` consult this to route an eager masked solve
    through the host-stepped driver (init/finalize jits + per-cycle jit)
    instead of the fused jitted program, so the hook can observe
    per-cycle liveness. False for plain ``trace_cycles`` hooks.
    """
    hook = _cycle_hook.get()
    return hook is not None and hook.masked


class LoopSpec(NamedTuple):
    """A solver's registration with the loop runtime.

    Build specs through a cached factory (``functools.lru_cache`` keyed by
    the solver's static knobs) so repeated solves hand the runtime the SAME
    spec object — the jitted drivers use the spec as a static argument and
    cache compiled cycles per (spec, sub-batch shape).
    """

    cycle: Callable        # state -> state, one heuristic cycle (all-live)
    live: Callable         # (state, rounds) -> (...,) bool per instance
    rounds_per_cycle: int
    lead_axes_fn: Callable | None = None   # (leaf, batch_ndim) -> int
    # optional per-instance heuristic-invocation counters, state -> (...,)
    # int (the balanced backend's ``heuristics``); folded into CycleEvent
    # .heur_total for detail hooks
    heur: Callable | None = None


class KindOps(NamedTuple):
    """A solver kind's instance operations over one set of static knobs.

    Build through the kind's cached factory (one ``functools.lru_cache``
    over its static knobs, registered as ``SolverKind.ops``) so every
    batch, refill session and warm start shares one ``LoopSpec`` and one
    set of jitted programs.  Problems and results use the kind's PUBLIC
    batched layout (batch axis leading on every leaf); ``init`` and
    ``finalize`` own any solver-internal re-layout.
    """

    spec: LoopSpec        # the kind's solver-loop registration
    init: Callable        # stacked problem -> loop state (jitted)
    finalize: Callable    # (stacked, state, rounds) -> batched result (jitted)
    solve: Callable       # stacked -> batched result: init, run_masked and
    #                       finalize in one jitted program
    pad_one: Callable     # (payload, bucket_shape) -> batch-1 stacked problem
    shape_of: Callable    # validated payload -> its shape tuple
    crop: Callable        # (result, b, shape, original) -> instance b cropped
    #                       to its original shape, one jitted dispatch


def solve(ops: KindOps, stacked, batch_shape: tuple, *, compact: bool = False,
          mesh=None, mesh_axis: str | None = None, state=None):
    """The one driver ladder: solve a stacked batch with a kind's ``ops``.

    Chooses, in order:

    * ``compact=True`` — early-exit compaction (``run_compacted``), one
      lane per shard when ``mesh`` is given (``compact_lanes``);
    * ``mesh`` — the fused masked program sharded over the batch axis
      through ``repro.launch.mesh.dispatch_sharded`` (looked up at call
      time);
    * a ``cycle_events(masked=True)`` hook on this thread — jitted init,
      an eager host-stepped ``run_masked``, jitted finalize (the same
      values as the fused program, with per-cycle events);
    * otherwise ``ops.solve``, one jitted dispatch.

    ``state`` starts the loop from a state already built (warm starts)
    instead of ``ops.init(stacked)``; such a solve never takes the fused
    programs, and on a mesh it runs as per-shard compacted lanes.
    """
    lanes = None
    if mesh is not None:
        if not compact and state is None:
            from repro.launch.mesh import dispatch_sharded
            return dispatch_sharded(ops.solve, (stacked,), batch_shape[0],
                                    mesh, mesh_axis)
        from repro.launch.mesh import compact_lanes
        lanes = compact_lanes(mesh, mesh_axis, batch_shape[0])
        compact = True
    if state is None:
        if not compact and not masked_events_active():
            return ops.solve(stacked)
        state = ops.init(stacked)
    state, rounds = drive(ops.spec, state, batch_shape, compact=compact,
                          lanes=lanes)
    return ops.finalize(stacked, state, rounds)


def drive(spec: LoopSpec, state, batch_shape: tuple, *, compact: bool = False,
          lanes=None, refill=None):
    """Run a built loop state to its end; returns ``(state, rounds)``.

    Compacted (``run_compacted`` on ``lanes``, with an optional refill
    hook) or masked: host-stepped under a ``cycle_events(masked=True)``
    hook, else one jitted loop.
    """
    if compact:
        return run_compacted(spec, state, batch_shape[0], lanes=lanes,
                             refill=refill)
    if masked_events_active():
        return run_masked(spec, state, batch_shape)
    return _run_masked_jit(spec, state, batch_shape)


def _lead(spec: LoopSpec, batch_ndim: int):
    """Adapt the spec's (leaf, batch_ndim) signature to a (leaf,) closure."""
    if spec.lead_axes_fn is None:
        return None
    fn = spec.lead_axes_fn
    return lambda a: fn(a, batch_ndim)


def run_masked(spec: LoopSpec, state, batch_shape: tuple):
    """Masked iteration: cycle the whole batch, freeze non-live instances.

    Jittable (it is the body both jitted solver entry points trace).  With
    ``batch_shape == ()`` the mask is the scalar predicate of a
    single-instance loop — the freeze select is the identity while it runs —
    so single and batched solves share one trajectory.

    Telemetry: an EAGER call under a ``cycle_events(masked=True)`` hook
    host-steps the same body one jitted cycle at a time (``_masked_step``)
    so per-cycle liveness reaches the hook — bit-identical results, since
    the per-cycle jit is the granularity the compacted driver already
    bit-matches at.  Inside a trace (tracer leaves) the hook cannot apply
    and the fused while_loop is lowered as always — jit caches never
    depend on the hook.

    Returns ``(state, rounds)`` where ``rounds`` counts, per instance, the
    Jacobi rounds executed while that instance was live.
    """
    hook = _cycle_hook.get()
    if (hook is not None and hook.masked
            and not any(isinstance(leaf, jax.core.Tracer)
                        for leaf in jax.tree_util.tree_leaves(state))):
        return _run_masked_stepped(spec, state, batch_shape, hook)

    lead = _lead(spec, len(batch_shape))

    def cond(carry):
        s, r = carry
        return jnp.any(spec.live(s, r))

    def body(carry):
        s, r = carry
        lv = spec.live(s, r)
        s = freeze(lv, spec.cycle(s), s, lead_axes_fn=lead)
        return s, r + jnp.where(lv, spec.rounds_per_cycle, 0)

    return jax.lax.while_loop(
        cond, body, (state, jnp.zeros(batch_shape, jnp.int32)))


_run_masked_jit = jax.jit(run_masked, static_argnames=("spec", "batch_shape"))


@functools.partial(jax.jit, static_argnames=("spec", "batch_ndim"))
def _masked_step(spec: LoopSpec, state, rounds, batch_ndim: int):
    """One masked cycle (exactly ``run_masked``'s while body) + next mask."""
    lead = _lead(spec, batch_ndim)
    lv = spec.live(state, rounds)
    s = freeze(lv, spec.cycle(state), state, lead_axes_fn=lead)
    r = rounds + jnp.where(lv, spec.rounds_per_cycle, 0)
    return s, r, spec.live(s, r)


def _masked_heur_total(spec: LoopSpec, state, live_mask) -> int | None:
    if spec.heur is None:
        return None
    h = np.asarray(_heur_vals(spec, state))
    return int(np.sum(h * np.asarray(live_mask)))


def _run_masked_stepped(spec: LoopSpec, state, batch_shape: tuple,
                        hook: "_CycleHook"):
    """Host-stepped masked driver: the telemetry path of ``run_masked``.

    Executes the identical per-cycle body through one jitted step per
    cycle, fetching the liveness mask between steps to emit
    ``CycleEvent``s.  The iteration count and every value match the fused
    while_loop (same cond-before-body structure, same freeze select).
    """
    bn = len(batch_shape)
    n_total = int(np.prod(batch_shape, dtype=np.int64)) if batch_shape else 1
    rounds = jnp.zeros(batch_shape, jnp.int32)
    lv = np.asarray(_live_mask(spec, state, rounds))
    cycle = 0
    while bool(np.any(lv)):
        heur_total = (_masked_heur_total(spec, state, lv)
                      if hook.detail else None)
        hook.fn(CycleEvent(
            driver="masked", cycle=cycle, n_live=int(np.sum(lv)),
            rounds_total=int(np.asarray(rounds).sum()),
            gathered=n_total, heur_total=heur_total))
        cycle += 1
        state, rounds, lv_next = _masked_step(spec, state, rounds, bn)
        lv = np.asarray(lv_next)
    return state, rounds


def bucket_size(n_live: int, cap: int) -> int:
    """Sub-batch size for ``n_live`` instances: next pow2, clamped to the
    lane size.  The fixed bucket ladder {1, 2, 4, ..., cap} bounds the
    number of distinct compiled cycle shapes to <= log2(cap) + 2."""
    p = 1 << max(0, n_live - 1).bit_length() if n_live > 1 else 1
    return min(p, cap)


def _tree_take(spec: LoopSpec, state, idx, batch_ndim: int = 1):
    """Gather instances ``idx`` from every leaf's batch axis."""
    lead = _lead(spec, batch_ndim)

    def take(a):
        return jnp.take(a, idx, axis=lead(a) if lead else 0)

    return jax.tree.map(take, state)


def _tree_put(spec: LoopSpec, state, idx, sub):
    """Scatter sub-batch ``sub`` back into ``state`` at instances ``idx``."""
    lead = _lead(spec, 1)

    def put(a, s):
        ax = lead(a) if lead else 0
        return a.at[(slice(None),) * ax + (idx,)].set(s)

    return jax.tree.map(put, state, sub)


@functools.partial(jax.jit, static_argnames=("spec",))
def _compact_step(spec: LoopSpec, state, rounds):
    """One cycle on an (all-live) compacted sub-batch + its next liveness."""
    new = spec.cycle(state)
    return new, spec.live(new, rounds + spec.rounds_per_cycle)


@functools.partial(jax.jit, static_argnames=("spec",))
def _live_mask(spec: LoopSpec, state, rounds):
    return spec.live(state, rounds)


@functools.partial(jax.jit, static_argnames=("spec",))
def _heur_vals(spec: LoopSpec, state):
    """Per-instance heuristic-invocation counters (detail hooks only)."""
    return spec.heur(state)


def _emit_slot(spec: LoopSpec, refill, token, lane_state, slot: int,
               rounds_val: int) -> None:
    """Hand one finished instance (a batch-1 gather of its slot) to the hook."""
    refill.emit(token, _tree_take(spec, lane_state, jnp.asarray([slot])),
                rounds_val)


def _admit_free(spec: LoopSpec, refill, lanes, lane_states, rounds,
                slot_token: list, live_idx: list, free_idx: list) -> None:
    """Offer freed slots to the refill hook until it declines or slots run out.

    Each admitted ``(token, state1)`` pair is scattered into the first free
    slot (device_put to the lane's device first, matching the initial
    placement), its rounds counter reset to 0, and its liveness evaluated
    EXACTLY as an initial instance's would be — born-dead admissions are
    emitted immediately with ``rounds == 0`` and never run a cycle, so an
    admitted instance's trajectory is indistinguishable from a solo solve.
    """
    while True:
        n_free = int(sum(f.size for f in free_idx))
        if n_free == 0:
            return
        new = refill.admit(n_free)
        if not new:
            return
        if len(new) > n_free:
            raise ValueError(
                f"refill.admit({n_free}) returned {len(new)} admissions; "
                f"it must return at most n_free")
        for token, st1 in new:
            i = next(j for j, f in enumerate(free_idx) if f.size)
            s = int(free_idx[i][0])
            free_idx[i] = free_idx[i][1:]
            lo, hi, dev = lanes[i]
            if dev is not None:
                st1 = jax.device_put(st1, dev)
            lane_states[i] = _tree_put(spec, lane_states[i],
                                       jnp.asarray([s]), st1)
            rounds[lo + s] = 0
            slot_token[lo + s] = token
            lv = _live_mask(spec, st1, jnp.zeros(1, jnp.int32))
            if bool(np.asarray(lv)[0]):
                live_idx[i] = np.sort(np.concatenate(
                    [live_idx[i],
                     np.asarray([s], dtype=live_idx[i].dtype)]))
            else:
                _emit_slot(spec, refill, token, lane_states[i], s, 0)
                free_idx[i] = np.concatenate(
                    [free_idx[i], np.asarray([s], dtype=free_idx[i].dtype)])


def _compacted_event(spec: LoopSpec, hook: "_CycleHook", cycle: int, lanes,
                     lane_states, live_idx, rounds) -> CycleEvent:
    """Build the pre-dispatch ``CycleEvent`` of one compacted host cycle."""
    gathered = sum(bucket_size(int(li.size), hi - lo)
                   for (lo, hi, _), li in zip(lanes, live_idx) if li.size)
    heur_total = None
    if hook.detail and spec.heur is not None:
        heur_total = 0
        for st, li in zip(lane_states, live_idx):
            if li.size:
                heur_total += int(np.asarray(_heur_vals(spec, st))[li].sum())
    return CycleEvent(
        driver="compacted", cycle=cycle,
        n_live=int(sum(li.size for li in live_idx)),
        rounds_total=int(rounds.sum()), gathered=gathered,
        heur_total=heur_total)


def run_compacted(spec: LoopSpec, state, n_instances: int, *, lanes=None,
                  refill=None):
    """Early-exit compaction over a 1-D batch axis of ``n_instances``.

    Between jitted cycle segments the host gathers still-live instances
    into a dense pow2-sized sub-batch (``bucket_size``), runs ``cycle`` on
    it, and scatters the results back in input order.  Pad slots of a
    bucket duplicate a live instance and are discarded on scatter — cycles
    are per-instance pure, so duplicates cannot perturb real slots.

    Args:
      spec: the solver's ``LoopSpec`` (from a cached factory).
      state: batched solver state; every leaf's batch axis has size
        ``n_instances`` at position ``lead_axes_fn(leaf, 1)``.
      n_instances: the batch size B.
      lanes: optional list of ``(lo, hi, device)`` contiguous slices (from
        ``repro.launch.mesh.compact_lanes``).  Each lane compacts
        independently on its device; instances never cross lanes.  Default:
        one lane covering the whole batch on the default device.
      refill: optional CONTINUOUS-BATCHING hook — an object with

        * ``admit(n_free) -> [(token, state1), ...]`` — called at every
          cycle boundary where slots are free (including before cycle 0 for
          instances that are born converged); returns at most ``n_free``
          new instances, each a caller-chosen token plus a batch-1 solver
          state (the kind's ``init`` of one padded problem).  Returning
          ``[]`` declines; the loop ends when nothing is live and the hook
          declines.
        * ``emit(token, state1, rounds)`` — called EXACTLY ONCE per
          instance, the moment it leaves the live set (converged or
          rounds-capped), with a batch-1 gather of its final state and its
          solo-accounting rounds counter.  Initial instances are emitted
          with their batch index as the token; admitted instances with the
          token ``admit`` returned.  Born-dead instances (initial or
          admitted) emit immediately with ``rounds == 0``.

        Admitted instances enter with a fresh rounds counter into the SAME
        gather/cycle/scatter machinery, so every emitted trajectory —
        values and counters — bit-matches that instance's solo solve
        (tests/test_refill.py).  ``refill=None`` (default) is exactly the
        closed-batch behaviour.

    Returns ``(state, rounds)`` — same contract as ``run_masked``; results
    bit-match it (tests/test_compact.py).  With ``refill`` the returned
    arrays describe the final slot OCCUPANTS (useful only for debugging) —
    per-instance results arrive through ``emit``.
    """
    if lanes is None:
        lanes = [(0, n_instances, None)]
    rounds = np.zeros(n_instances, np.int32)
    slot_token: list = list(range(n_instances))

    # Split into per-lane states (pinned to the lane's device, if any) and
    # evaluate initial liveness; fetch masks only after every lane has
    # dispatched so devices start concurrently.
    lane_states, masks, live_idx = [], [], []
    for lo, hi, dev in lanes:
        sub = _tree_take(spec, state, jnp.arange(lo, hi))
        if dev is not None:
            sub = jax.device_put(sub, dev)
        lane_states.append(sub)
        masks.append(_live_mask(spec, sub, jnp.zeros(hi - lo, jnp.int32)))
    for m in masks:
        live_idx.append(np.nonzero(np.asarray(m))[0])

    free_idx: list = []
    if refill is not None:
        # born-dead initial instances emit immediately (rounds = 0) and
        # free their slots for admission before the first cycle
        for i, (lo, hi, dev) in enumerate(lanes):
            dead = np.setdiff1d(np.arange(hi - lo, dtype=np.int64),
                                live_idx[i])
            for s in dead:
                _emit_slot(spec, refill, slot_token[lo + int(s)],
                           lane_states[i], int(s), 0)
            free_idx.append(dead)
        _admit_free(spec, refill, lanes, lane_states, rounds, slot_token,
                    live_idx, free_idx)

    hook = _cycle_hook.get()
    cycle = 0
    while any(li.size for li in live_idx):
        if hook is not None:
            hook.fn(_compacted_event(spec, hook, cycle, lanes, lane_states,
                                     live_idx, rounds))
        cycle += 1
        pending: list = [None] * len(lanes)
        for i, (lo, hi, dev) in enumerate(lanes):
            li = live_idx[i]
            if not li.size:
                continue
            m = bucket_size(int(li.size), hi - lo)
            pad = np.concatenate(
                [li, np.full(m - li.size, li[0], dtype=li.dtype)])
            sub = _tree_take(spec, lane_states[i], jnp.asarray(pad))
            new_sub, lv = _compact_step(
                spec, sub, jnp.asarray(rounds[lo:hi][pad]))
            # scatter ONLY the real slots: pad duplicates must not overwrite
            # their source instance with an extra-cycled value
            keep = _tree_take(spec, new_sub, jnp.arange(li.size))
            lane_states[i] = _tree_put(spec, lane_states[i],
                                       jnp.asarray(li), keep)
            pending[i] = lv
        for i, lv in enumerate(pending):   # host sync point, all lanes in
            if lv is None:
                continue
            li = live_idx[i]
            lo = lanes[i][0]
            rounds[lo + li] += spec.rounds_per_cycle
            keep_mask = np.asarray(lv)[:li.size]
            live_idx[i] = li[keep_mask]
            if refill is not None:
                done = li[~keep_mask]
                for s in done:
                    _emit_slot(spec, refill, slot_token[lo + int(s)],
                               lane_states[i], int(s),
                               int(rounds[lo + int(s)]))
                free_idx[i] = np.concatenate([free_idx[i], done])
        if refill is not None:
            _admit_free(spec, refill, lanes, lane_states, rounds,
                        slot_token, live_idx, free_idx)

    # Reassemble in input order (lanes are contiguous, ordered slices) on
    # the first lane's device, so a scheduler lane's sub-mesh keeps its
    # results instead of shipping them to another lane's chip.
    if len(lane_states) > 1:
        home = lanes[0][2]
        parts = [jax.device_put(s, home) for s in lane_states]
        lead = _lead(spec, 1)
        state = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=lead(xs[0]) if lead else 0),
            *parts)
    else:
        state = lane_states[0]
    return state, jnp.asarray(rounds)
