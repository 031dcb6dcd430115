"""Collective method plane — ANY registered device method, fabric-wide.

`parallel/mc_collective.py` proved the pipelined cross-controller session
shape (schedule once over the host plane, run K lockstep shard_map steps
with operands device-resident through the chain) — but its kernel was
hardcoded pmean, a canned demo. The single-controller fused dispatch
(`rpc/combo.py`) already runs arbitrary user-registered device methods
(`rpc/device_method.py`) with fingerprint validation, and the mc handshake
advertises those fingerprints (`transport/mc_link.py`) — this module
closes that loop, the way the reference transport carries *arbitrary*
registered methods rather than one canned op (protocol.h:64-158):

- **A session names a (service, method) pair.** The proposal carries the
  pair, the kernel fingerprint the proposer resolved, the row geometry,
  the step count and each party's initial operand. Nothing about the
  kernel's body crosses the wire — only its identity.
- **Every party validates before entering lockstep.** Each party — the
  proposer included — resolves the pair against its LOCAL registry and
  compares fingerprints. A mismatch (same name, different kernel — the
  divergence that would silently corrupt a lockstep chain) is a clean
  reject on the control stream: the proposer surfaces it before any
  party dispatches a collective that could never rendezvous.
- **The shared step binds the resolved kernel.** All parties jit the
  IDENTICAL program: ``shard_map`` over ``Mesh(parties, ("par",))`` —
  the SAME axis name the single-controller fused dispatch binds, so a
  kernel that reduces over the axis (psum gradients, all-to-all experts)
  behaves identically on both planes — applied K times with the chain's
  operands never leaving the devices.
- **N parties, convergent close.** The proposal fans out over the star
  (one host channel per remote party), a barrier collects every accept,
  and the final step count is the monotone max of every party's accept
  target — the 2-party close dance's ``max(targets)`` join generalized
  to N. All parties dispatch exactly ``final`` steps; each run response
  echoes the count and the proposer asserts convergence.

`ParallelChannel._fused_dispatch` lowers through this plane when its
sub-channels resolve to multi-controller links (one shard_map dispatch is
impossible across controllers — the client cannot place bytes on
non-addressable devices), so the single-controller fused path and the
cross-process path present ONE API: register a device method, call the
combo channel, and the transport picks the lowering.
"""

from __future__ import annotations

import base64
import contextlib
import json
import logging
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from incubator_brpc_tpu.bvar import Adder, LatencyRecorder, PassiveStatus
from incubator_brpc_tpu.parallel.collective import launch_order as _launch_order
from incubator_brpc_tpu.utils.flags import define_flag, get_flag

logger = logging.getLogger(__name__)

define_flag(
    "mc_dispatch_min_steps",
    0,
    "minimum step count this party accepts into a collective-method "
    "session: its accept ack raises the session target to at least this "
    "(the proposer folds every target with max — the N-party join)",
    lambda v: v >= 0,
)

define_flag(
    "mc_dispatch_session_deadline_ms",
    0,
    "default per-session deadline for collective-method sessions: a "
    "session older than this aborts fabric-wide with ESESSION (every "
    "party watches its own copy, so a partitioned party still unwedges); "
    "0 = inherit the proposal's RPC timeout",
    lambda v: v >= 0,
)

define_flag(
    "mc_dispatch_checkpoint_every",
    0,
    "checkpoint cadence (in lockstep steps) for collective-method "
    "sessions: every C completed steps each party retains its "
    "device-resident operand shards in a ring, so an aborted session can "
    "resume from the last COMMON checkpoint instead of step 0; the "
    "proposer stamps the cadence into the run proposal so every party "
    "checkpoints the same steps; 0 = checkpointing off (abort = restart)",
    lambda v: v >= 0,
)

define_flag(
    "mc_dispatch_checkpoint_depth",
    4,
    "ring depth of the per-party checkpoint store: how many checkpointed "
    "steps stay device-resident per session (older entries are evicted "
    "oldest-first; memory cost per entry is parties x width bytes)",
    lambda v: v >= 1,
)

define_flag(
    "mc_dispatch_step_deadline_ms",
    0,
    "per-STEP watchdog for collective-method sessions: a single lockstep "
    "step (dispatch-to-dispatch progress, or the final fetch) stalled "
    "longer than this aborts the session fabric-wide — bounding a wedge "
    "INSIDE one step instead of waiting out the whole session deadline; "
    "0 = off (the session deadline is the only backstop)",
    lambda v: v >= 0,
)

DISPATCH_METHOD = "collective_dispatch"

# Bounds a proposal must sit inside before anything is resolved or run
# (mirrors mc_collective's admission checks).
MAX_STEPS = 100_000
MAX_WIDTH = 1 << 20
MAX_PARTIES = 1024
# chunked overlap sessions (T3): a step's operand may split into at most
# this many independently-dispatched sub-collectives — past ~64 the
# per-chunk dispatch overhead swamps the overlap win (docs/DEVICE_PLANE.md)
MAX_CHUNKS = 64

# plane-level observability: sessions/steps/errors/rejects across every
# kernel, plus a latency summary; per-kernel counters are minted lazily
# below so /vars and /brpc_metrics can tell WHICH methods ride the plane
dispatch_sessions = Adder(name="mc_dispatch_sessions")
dispatch_steps = Adder(name="mc_dispatch_steps")
dispatch_errors = Adder(name="mc_dispatch_errors")
dispatch_rejects = Adder(name="mc_dispatch_rejects")
dispatch_aborts = Adder(name="mc_dispatch_aborts")
dispatch_resumes = Adder(name="mc_dispatch_resumes")
dispatch_replaced_parties = Adder(name="mc_dispatch_replaced_parties")
dispatch_session_us = LatencyRecorder(name="mc_dispatch_session_us")
# the overlap scheduler's proof-of-overlap counters: chunk sub-collectives
# dispatched, and how many of them were dispatched while the SAME slice's
# predecessor collective was still in flight (the non-blocking ack probe
# said not-ready) — their ratio is the measured overlap, scrapeable as
# mc_dispatch_overlap_ratio.  Tallied once per session, not per chunk.
dispatch_chunks = Adder(name="mc_dispatch_chunks")
dispatch_overlapped_chunks = Adder(name="mc_dispatch_overlapped_chunks")
# the quantized-collective plane (parallel/quantized.py): sessions that
# ran a quantized kernel variant, and the cumulative wire bytes the
# quantization removed vs the same session at exact float32 width
# (parties x replayed steps x (width - quantized wire bytes), tallied
# once per session)
dispatch_quantized_sessions = Adder(name="mc_dispatch_quantized_sessions")
dispatch_bytes_saved = Adder(name="mc_dispatch_bytes_saved")


def _overlap_ratio() -> float:
    total = dispatch_chunks.get_value()
    if not total:
        return 0.0
    return dispatch_overlapped_chunks.get_value() / total


overlap_ratio_gauge = PassiveStatus(
    _overlap_ratio, name="mc_dispatch_overlap_ratio"
)

_method_counters: Dict[Tuple[str, str], Adder] = {}
_method_counters_lock = threading.Lock()


def _method_counter(service: str, method: str) -> Adder:
    """Per-kernel session counter (``mc_dispatch_<svc>_<m>_sessions``),
    minted on first use — the bvar registry keeps it scrapeable."""
    key = (service, method)
    with _method_counters_lock:
        ctr = _method_counters.get(key)
        if ctr is None:
            safe = "_".join(
                "".join(c if c.isalnum() else "_" for c in part)
                for part in key
            )
            ctr = Adder(name=f"mc_dispatch_{safe}_sessions")
            _method_counters[key] = ctr
        return ctr


# -- session fault plane -------------------------------------------------------
#
# A session is no longer fire-and-forget: every party (proposer included)
# registers it here with a deadline and an abort event.  Death of a party
# — detected from the proposer's failed run RPC, a dying control socket,
# or a device/mc link's fail() hook — aborts the session FABRIC-WIDE: an
# abort broadcast (phase:"abort") plus each party's own deadline watch
# makes every survivor exit the lockstep chain with a clean ESESSION
# instead of hanging in a barrier the dead party can never join.


class SessionAborted(RuntimeError):
    """A collective session aborted (party death, deadline, or reject).

    ``dead_indexes``/``survivor_indexes`` are party positions in the
    proposal's mesh order — the re-propose path runs the next session
    over exactly ``survivor_indexes``."""

    def __init__(
        self,
        reason: str,
        dead_indexes=(),
        survivor_indexes=(),
        rejects=(),
        session_id: str = "",
        final_steps: int = 0,
    ):
        super().__init__(reason)
        from incubator_brpc_tpu.utils.status import ErrorCode

        self.error_code = int(ErrorCode.ESESSION)
        self.reason = reason
        self.dead_indexes = tuple(dead_indexes)
        self.survivor_indexes = tuple(survivor_indexes)
        self.rejects = tuple(rejects)  # (index, error_text) non-death fails
        # what the resume path needs: the aborted session's identity (its
        # checkpoint rings are keyed on it) and the agreed step count the
        # resumed run must still converge to
        self.session_id = session_id
        self.final_steps = int(final_steps)


class _SessionState:
    __slots__ = (
        "session_id", "party_ids", "owner", "deadline", "abort_event",
        "abort_reason", "aborted", "epoch",
    )

    def __init__(self, session_id, party_ids, deadline, owner, epoch=0):
        self.session_id = session_id
        self.party_ids = tuple(party_ids)
        self.owner = owner  # the serving Server (None on the proposer)
        self.deadline = deadline  # absolute monotonic seconds (0 = none)
        self.abort_event = threading.Event()
        self.abort_reason = ""
        self.aborted = False
        # which RUN of this session this registrant belongs to: a RESUMED
        # run re-registers the SAME session id at epoch+1, and an abort
        # broadcast stamped with an older epoch (a straggler from the
        # aborted first run — delayed delivery, or a retry that rode a
        # fresh connection and lost FIFO with the resume proposal) must
        # not kill the healed run
        self.epoch = int(epoch)


# session id -> every local registrant (proposer AND parties: in a
# single-controller run — and the in-process tests — several parties of
# ONE session live in one process; an abort must unwedge all of them)
_sessions: Dict[str, List[_SessionState]] = {}
_sessions_lock = threading.Lock()

# abort tombstones: session id -> highest epoch aborted SO FAR.  An abort
# only flips registrants that exist when it lands — a run proposal of an
# already-aborted epoch arriving AFTER the abort would otherwise register
# fresh and start a zombie chain no peer will ever join (unwedged only by
# its own deadline).  The tombstone closes that race: such proposals are
# rejected ESESSION at admission.  A RESUMED run (epoch+1) stays
# admissible — the tombstone only covers epochs the proposer already gave
# up on.  Insertion-ordered, capped (dead sessions age out).
_MAX_TOMBSTONES = 256
_aborted_epochs: Dict[str, int] = {}


def aborted_epoch(session_id: str) -> int:
    """Highest aborted epoch for a session (-1 = never aborted here)."""
    with _sessions_lock:
        return _aborted_epochs.get(session_id, -1)


def _register_session(session_id, party_ids, deadline, owner=None, epoch=0):
    st = _SessionState(session_id, party_ids, deadline, owner, epoch=epoch)
    with _sessions_lock:
        _sessions.setdefault(session_id, []).append(st)
    return st


def _unregister_session(st: _SessionState) -> None:
    with _sessions_lock:
        states = _sessions.get(st.session_id)
        if states is not None:
            try:
                states.remove(st)
            except ValueError:
                pass
            if not states:
                del _sessions[st.session_id]


def active_sessions(owner=None) -> int:
    """Live (registered, not yet closed) session registrations — all of
    them, or only those served by ``owner`` (Server.enter_lame_duck
    drains its own)."""
    with _sessions_lock:
        return sum(
            1
            for states in _sessions.values()
            for st in states
            if owner is None or st.owner is owner
        )


def abort_session(
    session_id: str, reason: str, epoch: Optional[int] = None
) -> bool:
    """Flip local registrants of one session to aborted (idempotent;
    counted once per session per process).  ``epoch`` scopes the abort to
    registrants of that run or older — a stale broadcast from an aborted
    first run cannot kill the session's RESUMED run (epoch+1); None
    aborts every registrant (link death, local sweeps).  Returns False
    when nothing matched — already closed, never registered here, or all
    registrants newer than the stamped epoch; all fine for a best-effort
    broadcast."""
    with _sessions_lock:
        states = [
            st
            for st in _sessions.get(session_id, ())
            if epoch is None or st.epoch <= epoch
        ]
        # tombstone the aborted epoch(s): a run proposal for an epoch ≤
        # this arriving LATER (reordered past the abort) must not start a
        # zombie chain.  An epoch-stamped abort tombstones even with no
        # registrant yet — the abort-beats-proposal ordering; an unstamped
        # (local) abort tombstones whatever it actually hit.
        stone = epoch if epoch is not None else max(
            (st.epoch for st in states), default=None
        )
        if stone is not None and _aborted_epochs.get(session_id, -1) < stone:
            while len(_aborted_epochs) >= _MAX_TOMBSTONES:
                _aborted_epochs.pop(next(iter(_aborted_epochs)))
            _aborted_epochs[session_id] = stone
        if not states:
            return False
        first = any(not st.aborted for st in states)
        for st in states:
            st.aborted = True
            if not st.abort_reason:
                st.abort_reason = reason
    if first:
        dispatch_aborts << 1
        logger.warning("mc_dispatch session %s aborted: %s", session_id, reason)
    for st in states:
        st.abort_event.set()
    return True


def abort_sessions_for_owner(owner, reason: str) -> int:
    """Abort every session served by one Server — the chaos drill's
    clean-death seam (a killed party's own handler must unwedge promptly
    instead of burning its session deadline) and a stop-time sweep for
    anything that outlived a drain. Returns the number of sessions hit."""
    with _sessions_lock:
        hit = [
            sid for sid, states in _sessions.items()
            if any(st.owner is owner for st in states)
        ]
    for sid in hit:
        abort_session(sid, reason)
    return len(hit)


def abort_sessions_for_devices(device_ids, reason: str) -> int:
    """Link-death feedback (transport/device_link fail() calls here): any
    active session with a party on one of these GLOBAL device ids aborts —
    the link that carried the lockstep traffic is gone, so the chain can
    never converge. Returns the number of sessions aborted."""
    dead = set(int(d) for d in device_ids)
    with _sessions_lock:
        hit = [
            sid for sid, states in _sessions.items()
            if any(dead & set(st.party_ids) for st in states)
        ]
    for sid in hit:
        abort_session(sid, reason)
    return len(hit)


# -- step-granular checkpoint rings --------------------------------------------
#
# The elastic half of the fault plane: with ``mc_dispatch_checkpoint_every``
# set, each party retains a device-resident ring of its last
# ``mc_dispatch_checkpoint_depth`` completed-step operand shards, keyed by
# (session_id, own party index).  An aborted session's rings survive the
# abort so the resume barrier can agree on the last COMMON checkpointed
# step (the min-join over survivor watermarks) and replay only the steps
# past it.  Rings are released by the proposer's phase:"release" broadcast
# on clean completion (or after a finished resume) and capped by an
# oldest-session eviction so a crashed proposer cannot pin device memory
# forever.  Entries hold the session's GLOBAL jax arrays — retaining them
# is free (no host sync; the buffers just stay alive on their devices).

_MAX_CHECKPOINT_SESSIONS = 16


class _QuantCk:
    """A quantized checkpoint payload: the ring entry of a QUANTIZED
    session stores the block-quantized representation (values + int8
    scale exponents) instead of the float32 rows — the same ~4x the wire
    saves, applied to the ring's device memory (the gauge below reflects
    it).  Power-of-two scales make dequantize→requantize exactly
    idempotent (parallel/quantized.py), so a chain restored from this
    entry replays byte-identically to the undisturbed run."""

    __slots__ = ("q", "e", "mode", "block", "width")

    def __init__(self, q, e, mode: str, block: int, width: int):
        self.q = q
        self.e = e
        self.mode = mode
        self.block = int(block)
        self.width = int(width)

    def arrays(self):
        return (self.q, self.e)

    def shard_row(self, dev):
        """Materialize the full-width uint8 row retained for one device
        (host-side dequantize via the numpy twin — bitwise equal to the
        jax arithmetic, resume-path only), or None when this payload
        holds no shard on that device."""
        from incubator_brpc_tpu.parallel import quantized as _quantized

        q_sh = next(
            (s for s in self.q.addressable_shards if s.device == dev), None
        )
        e_sh = next(
            (s for s in self.e.addressable_shards if s.device == dev), None
        )
        if q_sh is None or e_sh is None:
            return None
        f = _quantized.np_dequantize(
            np.asarray(q_sh.data).reshape(-1),
            np.asarray(e_sh.data).reshape(-1),
            self.mode,
            self.block,
        )
        row = np.frombuffer(f.astype(np.float32).tobytes(), dtype=np.uint8)
        return row.copy()


def _payload_arrays(payload):
    """The jax arrays inside a ring payload — raw row array, or the
    quantized pair — for readiness probes."""
    if isinstance(payload, _QuantCk):
        return payload.arrays()
    return (payload,)


def _payload_shard_row(payload, dev) -> Optional[np.ndarray]:
    """Full-width uint8 row this payload retains on one device, or
    None.  One accessor for both entry formats so the reshard and
    restore paths cannot diverge on representation."""
    if isinstance(payload, _QuantCk):
        return payload.shard_row(dev)
    sh = next(
        (s for s in payload.addressable_shards if s.device == dev), None
    )
    if sh is None:
        return None
    return np.asarray(sh.data).reshape(-1).astype(np.uint8)


class _CheckpointRing:
    __slots__ = ("session_id", "own_index", "party_ids", "entries",
                 "entry_bytes")

    def __init__(self, session_id, own_index, party_ids, entry_bytes):
        self.session_id = session_id
        self.own_index = int(own_index)
        self.party_ids = tuple(party_ids)
        self.entries = []  # ascending [(completed_step, x, ns)]
        self.entry_bytes = int(entry_bytes)  # retained bytes per entry

    def put(self, step: int, x, ns, depth: int) -> None:
        # a RESUMED run replays step numbers the aborted run already
        # checkpointed: the fresh entry REPLACES the stale one (which may
        # be wedged behind the dead party's collective and never become
        # ready) — duplicates would make get() hand back the stale arrays
        step = int(step)
        self.entries = [e for e in self.entries if e[0] != step]
        self.entries.append((step, x, ns))
        self.entries.sort(key=lambda e: e[0])
        while len(self.entries) > depth:
            self.entries.pop(0)

    @staticmethod
    def _ready(x, ns) -> bool:
        """Checkpoints are retained at DISPATCH time (the chain is
        async); an entry only counts toward the resume census once its
        buffers are actually computed — a step wedged behind a dead
        party's collective must never be elected as the resume point
        (materializing it would hang the resume barrier itself)."""
        for arr in (*_payload_arrays(x), ns):
            fn = getattr(arr, "is_ready", None)
            if callable(fn):
                try:
                    if not fn():
                        return False
                except Exception:  # noqa: BLE001 — runtime quirk: count it
                    pass
        return True

    def watermark(self) -> int:
        steps = self.steps()
        return max(steps) if steps else 0

    def steps(self):
        return [s for s, x, n in self.entries if self._ready(x, n)]

    def get(self, step: int):
        for s, x, ns in self.entries:
            if s == step:
                return x, ns
        return None


# session id -> {own_index: ring}; insertion-ordered for eviction
_checkpoints: Dict[str, Dict[int, _CheckpointRing]] = {}
_checkpoints_lock = threading.Lock()


def _checkpoint_ring(session_id, own_index, party_ids, entry_bytes):
    """Get-or-create the ring for one party of one session (evicting the
    oldest session past the cap — bounded device memory, not a leak).
    Eviction prefers sessions with no LIVE registrant: a churning fleet
    of short sessions must not silently strip a long-running session of
    the very checkpoints its resume depends on.  (The live set is
    snapshotted before taking the ring lock — no lock nesting.)"""
    with _sessions_lock:
        live = set(_sessions)
    with _checkpoints_lock:
        rings = _checkpoints.get(session_id)
        if rings is None:
            while len(_checkpoints) >= _MAX_CHECKPOINT_SESSIONS:
                victim = next(
                    (s for s in _checkpoints if s not in live),
                    next(iter(_checkpoints)),  # all live: cap still wins
                )
                _checkpoints.pop(victim)
            rings = _checkpoints.setdefault(session_id, {})
        ring = rings.get(int(own_index))
        if ring is None:
            ring = _CheckpointRing(
                session_id, own_index, party_ids, entry_bytes
            )
            rings[int(own_index)] = ring
        return ring


def _checkpoint_lookup(session_id, own_index):
    with _checkpoints_lock:
        return _checkpoints.get(session_id, {}).get(int(own_index))


def checkpoint_watermarks(session_id: str) -> Dict[int, dict]:
    """Every LOCAL party's checkpoint census for one session — what a
    phase:"resume_query" answers: {party index: {"watermark": last
    checkpointed step, "steps": retained steps}}."""
    with _checkpoints_lock:
        rings = list(_checkpoints.get(session_id, {}).values())
    return {
        r.own_index: {"watermark": r.watermark(), "steps": r.steps()}
        for r in rings
    }


def release_checkpoints(session_id: str) -> bool:
    """Drop every local ring of one session (the proposer broadcasts this
    on clean completion; idempotent)."""
    with _checkpoints_lock:
        return _checkpoints.pop(session_id, None) is not None


def checkpoint_bytes_retained() -> int:
    """Device bytes pinned by checkpoint rings across every session —
    the cost side of the checkpoint-depth tradeoff, scrapeable."""
    with _checkpoints_lock:
        return sum(
            len(r.entries) * r.entry_bytes
            for rings in _checkpoints.values()
            for r in rings.values()
        )


checkpoint_bytes_gauge = PassiveStatus(
    checkpoint_bytes_retained, name="mc_dispatch_checkpoint_bytes"
)


def _checkpoint_rows(
    session_id: str, step: int, slots
) -> Dict[int, Tuple[bytes, int]]:
    """Materialize checkpointed rows for the requested party slots at one
    step, from ANY local ring that addresses them — the reshard source a
    replacement party is bootstrapped from.  Returns {slot: (full-width
    row bytes, n)} for every slot this process can serve (possibly
    empty).  This is the one host-blocking checkpoint operation, and it
    only runs on the resume path."""
    import jax  # noqa: F401 — device access below

    want = [int(s) for s in slots]
    with _checkpoints_lock:
        rings = list(_checkpoints.get(session_id, {}).values())
    out: Dict[int, Tuple[bytes, int]] = {}
    for ring in rings:
        entry = ring.get(int(step))
        if entry is None:
            continue
        x, ns = entry
        by_dev_n = {s.device: s for s in ns.addressable_shards}
        for slot in want:
            if slot in out or not (0 <= slot < len(ring.party_ids)):
                continue
            try:
                dev = _devices_by_id([ring.party_ids[slot]])[0]
            except ValueError:
                continue
            # the wire format is always the FULL-WIDTH row: a quantized
            # ring dequantizes here (exact — power-of-two scales), so
            # the reshard protocol never forks on representation
            row = _payload_shard_row(x, dev)
            sn = by_dev_n.get(dev)
            if row is None or sn is None:
                continue
            out[slot] = (
                row.tobytes(),
                int(np.asarray(sn.data).reshape(-1)[0]),
            )
    return out


def checkpoint_fetch(session_id: str, step: int, slots) -> Dict[int, dict]:
    """The wire form of :func:`_checkpoint_rows` (phase:"fetch_shard"):
    {slot: {"row": b64 full-width row bytes, "n": int}}."""
    return {
        slot: {"row": base64.b64encode(row).decode(), "n": int(n)}
        for slot, (row, n) in _checkpoint_rows(session_id, step, slots).items()
    }


def resume_point(watermarks: Dict[int, Optional[dict]]) -> int:
    """The resume barrier's join: the last COMMON checkpointed step over
    the survivors — ``min`` over their watermarks, the dual of the accept
    phase's ``max`` join (a session can only resume from a step EVERY
    survivor retained, just as it can only run a count every party
    accepted).  ``watermarks[slot]`` is a resume_query answer ({"watermark",
    "steps"}) or None for a survivor that answered nothing.  Any survivor
    with no checkpoint drags the join to 0 — the full-restart fallback.
    The min is validated against every retained set (rings are
    cadence-uniform, but an evicted entry must not be resumed from): when
    the min is not common, the join falls back to the deepest step ALL
    survivors still retain, then to 0."""
    if not watermarks:
        return 0
    infos = list(watermarks.values())
    if any(not info or int(info.get("watermark", 0)) <= 0 for info in infos):
        return 0
    point = min(int(info["watermark"]) for info in infos)
    sets = [frozenset(int(s) for s in info.get("steps", ())) for info in infos]
    if all(point in s for s in sets):
        return point
    common = frozenset.intersection(*sets) if sets else frozenset()
    return max((s for s in common if s <= point), default=0)


# Between-step seam: chaos drills park parties here (deterministically
# mid-session) and production leaves it None.  Called as fn(step_index)
# — or fn(step_index, own_index) / fn(step_index, own_index, chunk) when
# it accepts more arguments, so a drill can target ONE party, or one
# CHUNK of a step (half-acked-step chaos) — before each lockstep step
# (1/2-arg forms fire once per step; the 3-arg form fires before every
# chunk dispatch of a chunked overlap session).
_step_hook: Optional[Callable] = None


def set_step_hook(fn: Optional[Callable]) -> None:
    global _step_hook
    if fn is not None:
        import inspect

        try:
            nparams = len(inspect.signature(fn).parameters)
        except (TypeError, ValueError):
            nparams = 1
        if nparams < 2:
            inner1 = fn
            fn = (  # noqa: E731
                lambda step, idx, chunk, _f=inner1:
                _f(step) if chunk == 0 else None
            )
        elif nparams < 3:
            inner2 = fn
            fn = (  # noqa: E731
                lambda step, idx, chunk, _f=inner2:
                _f(step, idx) if chunk == 0 else None
            )
    _step_hook = fn


# -- kernel resolution ---------------------------------------------------------

# Fallback resolvers for builtin kernels that are minted per-geometry
# rather than registered by a Server (mc_collective's pmean installs one).
# Signature: (service, method, width_bytes) -> Optional[DeviceMethod].
_resolvers: List[Callable] = []


def register_method_resolver(fn: Callable) -> None:
    if fn not in _resolvers:
        _resolvers.append(fn)


def resolve_method(service: str, method: str, width: Optional[int] = None):
    """Resolve (service, method) to this process's DeviceMethod: the
    process-global registry first (what Server.add_service fills), then
    the builtin resolvers. ``width`` (row bytes) must match the resolved
    geometry — a session whose parties disagree on geometry could never
    exchange shards."""
    from incubator_brpc_tpu.rpc.device_method import lookup_device_method

    dm = lookup_device_method(service, method)
    if dm is None:
        for r in list(_resolvers):
            dm = r(service, method, width)
            if dm is not None:
                break
    if dm is None:
        return None
    if width is not None and dm.width != width:
        return None
    return dm


def _devices_by_id(ids: List[int]):
    import jax

    by_id = {d.id: d for d in jax.devices()}
    try:
        return [by_id[i] for i in ids]
    except KeyError as e:
        raise ValueError(
            f"device id {e} not in this process's global view "
            f"(is jax.distributed initialized everywhere?)"
        )


# -- the shared lockstep step --------------------------------------------------


_step_cache: Dict[tuple, tuple] = {}  # (fp, party ids) -> (step_fn, dm)
# chunk split/concat programs: (party ids, width, chunks) -> (split, concat)
_chunk_ops_cache: Dict[tuple, tuple] = {}
# checkpoint quantizers: (party ids, width, mode, block) -> jitted qz
_ck_quant_cache: Dict[tuple, object] = {}
_step_cache_lock = threading.Lock()  # guards ALL three caches (never nested)
# One process may host several parties of a session (single-controller
# runs, the in-process tests): their threads dispatch the same
# multi-device collective program concurrently, and take the process's one
# launch order (parallel/collective.py, shared with the fused combo
# dispatch) across the enqueue.  A process that addresses ONE device of
# the party set (the one-party-per-process deployment) has no co-hosted
# launch to order and never takes it.
_no_launch_order = contextlib.nullcontext()


def _make_step(dm, mesh, sharding, party_ids):
    """The identical jitted program every party dispatches: one shard_map
    application of the resolved kernel over the party axis. Axis name
    "par" matches the single-controller fused dispatch (rpc/combo.py), so
    axis-reducing kernels produce the same bytes on both planes. Cached
    per (kernel fingerprint, party set): the ParallelChannel lowering
    runs one session per combo CALL, and re-tracing every call would put
    XLA compilation on the request path (combo's _fused_cache, here).
    Overlap sessions call the SAME cached program at a chunk's width —
    jit re-specializes per input shape, and a chunk-safe kernel applied
    to a slice yields the slice of the full-width result, so the chunked
    chain's bytes match the unchunked chain's."""
    import jax
    from jax.sharding import PartitionSpec as P

    key = (dm.fingerprint(), tuple(party_ids))
    with _step_cache_lock:
        cached = _step_cache.get(key)
        if cached is not None and cached[1] is not dm:
            cached = None  # same name re-registered with a new DeviceMethod
        if cached is None:

            def body(data, ns):
                out, m = dm.kernel(data[0], ns[0])
                return out[None], m[None]

            wrapped = jax.shard_map(
                body, mesh=mesh, in_specs=(P("par"), P("par")),
                out_specs=(P("par"), P("par")), check_vma=False,
            )
            cached = (
                jax.jit(wrapped, out_shardings=(sharding, sharding)), dm
            )
            _step_cache[key] = cached
    return cached[0]


def _make_chunk_ops(mesh, sharding, width: int, chunks: int, party_ids):
    """Jitted split/concat between the full-width session row and its C
    leading-axis chunks.  Pure per-shard slicing — NO collectives, so the
    parties need no rendezvous to run them, and both directions dispatch
    async (the operands never leave their devices).  Cached like the step
    program: re-tracing per session would put XLA on the request path."""
    import jax
    import jax.numpy as jnp

    key = (tuple(party_ids), int(width), int(chunks))
    with _step_cache_lock:
        cached = _chunk_ops_cache.get(key)
        if cached is None:
            cw = width // chunks

            def split(full):
                return tuple(
                    full[:, j * cw:(j + 1) * cw] for j in range(chunks)
                )

            def concat(*parts):
                return jnp.concatenate(parts, axis=1)

            cached = (
                jax.jit(split, out_shardings=(sharding,) * chunks),
                jax.jit(concat, out_shardings=sharding),
            )
            _chunk_ops_cache[key] = cached
    return cached


def _make_ck_quant(mesh, sharding, dm, party_ids):
    """Jitted checkpoint quantizer for a quantized session: global uint8
    rows (n, width) -> (wire values, int8 exponents), both sharded over
    the party axis.  Pure per-row arithmetic — no collectives, so the
    parties need no rendezvous and the dispatch stays async (retaining
    the quantized arrays IS the checkpoint, same as the raw path).
    Cached like the step program."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from incubator_brpc_tpu.parallel.quantized import _jq_quantize

    mode, block = dm.quant_mode, dm.quant_block
    key = (tuple(party_ids), int(dm.width), mode, int(block))
    with _step_cache_lock:
        cached = _ck_quant_cache.get(key)
        if cached is None:
            out_sh = NamedSharding(mesh, P("par"))

            def qz(x, _m=mode, _b=block):
                import jax.numpy as jnp

                f = jax.lax.bitcast_convert_type(
                    x.reshape(x.shape[0], -1, 4), jnp.float32
                )
                return _jq_quantize(f, _m, _b)

            cached = jax.jit(qz, out_shardings=(out_sh, out_sh))
            _ck_quant_cache[key] = cached
    return cached


# fabriclint: hotpath
def _chunk_ready(arr) -> bool:
    """Non-blocking chunk-ack probe — the overlap scheduler's per-chunk
    observation point.  Reads the buffer's completion state without
    synchronizing (``jax.Array.is_ready``); a runtime without the probe
    reports ready, which only degrades telemetry, never correctness (the
    device executes the chunk chain in dataflow order regardless)."""
    fn = getattr(arr, "is_ready", None)
    if fn is None:
        return True
    try:
        return bool(fn())
    except Exception:  # noqa: BLE001 — runtime quirk: assume complete
        return True


def _await_or_abort(arr, should_abort) -> None:
    """Wait for ``arr`` as the blocking fetch would, but leave with
    SessionAborted once the session aborts.  Dispatches are async, so a
    survivor has usually queued every step before a peer dies; the
    collective the dead party never joins then never completes, and the
    runtime's own timeout (Gloo on the CPU mesh: 30 minutes) is no bound.
    The abandoned buffers stay queued behind that collective; the healed
    session runs over a different party set and does not wait for them.
    Returns with EVERY addressable shard of ``arr`` complete (a
    single-controller run holds them all: the checkpoint census counts a
    step only once all of it is ready) and raises what the chain raised."""
    import jax

    delay = 50e-6
    while should_abort is not None and not _chunk_ready(arr):
        why = should_abort()
        if why:
            raise SessionAborted(why)
        time.sleep(delay)
        delay = min(delay * 2, 1e-3)
    jax.block_until_ready(arr)


def _validate_chunks(dm, chunks, service: str, method: str) -> int:
    """Chunk admission, identical at every seam (proposer, accepting
    party's handler, the session runner): one copy so a future rule
    change can never let a proposal through one seam that another
    rejects.  Returns the normalized chunk count; raises ValueError
    (the handlers map it to a clean EREQUEST reject before lockstep)."""
    chunks = int(chunks or 1)
    if not (1 <= chunks <= MAX_CHUNKS):
        raise ValueError(f"chunks {chunks} outside 1..{MAX_CHUNKS}")
    if dm.width % chunks != 0:
        raise ValueError(
            f"chunks {chunks} does not divide method width {dm.width}"
        )
    if chunks > 1 and not getattr(dm, "chunkable", False):
        # chunk-safety is a registration-time declaration: a mismatch
        # must reject before lockstep, exactly like a fingerprint
        # mismatch — a silently mis-chunked kernel would diverge, not
        # fail
        raise ValueError(
            f"device method {service}.{method} is not registered "
            "chunkable (chunked overlap sessions need the chunk-safety "
            "declaration)"
        )
    align = int(getattr(dm, "chunk_align", 1) or 1)
    if chunks > 1 and (dm.width // chunks) % align != 0:
        # block-wise quantized kernels: a chunk cut mid-scale-block
        # would recompute block scales from partial blocks and diverge
        # from the full-width bytes — alignment is part of chunk-safety
        raise ValueError(
            f"chunk width {dm.width // chunks} is not a multiple of "
            f"{service}.{method}'s {align}-byte block alignment"
        )
    return chunks


def _validate_chunk_order(chunk_order, chunks: int) -> List[int]:
    """Session-uniform chunk dispatch order (the topology-aware
    scheduler's stamp): None is mesh order; anything else must be a
    permutation of the chunk set — every party dispatches the same
    sub-collective sequence or the chunk collectives cannot
    rendezvous.  Raises ValueError (handlers reject EREQUEST)."""
    if chunk_order is None:
        return list(range(chunks))
    order = [int(j) for j in chunk_order]
    if sorted(order) != list(range(chunks)):
        raise ValueError(
            f"chunk_order {order} is not a permutation of 0..{chunks - 1}"
        )
    return order


# -- topology-aware scheduling (TASP, PAPERS.md 2509.26541) --------------------
#
# The N-party fan-out and the chunk routes were dispatched in MESH order
# — blind to the fabric.  The DeviceLinkMap star has been measuring
# per-link rtt and bytes/s since PR 1; `link_profile()` (transport/
# device_link.py) snapshots those recorders, and the scheduler orders
# work by MEASURED speed instead: the slowest link's party is proposed
# to first — it needs the longest lead before every barrier (the TASP
# rule: schedule the scarce link before the fast ones).  The chunk
# dispatch order is derived from the same profile (see
# schedule_session_order for exactly what that does and does not buy —
# chunk sub-collectives are symmetric across links).  The chosen order
# and the profile it came from are stamped into the run proposal and
# the rpcz session span, so a surprising schedule is auditable after
# the fact.


def _profile_speed(info) -> Optional[tuple]:
    """Sort key for one party's measured link: (GB/s ascending, rtt
    DESCENDING) — slowest first; None when the link has no telemetry
    (no evidence of being slow: it keeps mesh order at the tail)."""
    if not info:
        return None
    gbps = float(info.get("gbps", 0.0) or 0.0)
    rtt = float(info.get("rtt_us", 0.0) or 0.0)
    if gbps <= 0.0 and rtt <= 0.0:
        return None
    return (gbps, -rtt)


def schedule_session_order(
    party_ids: List[int], profile, chunks: int = 1
) -> Tuple[List[int], List[int], str]:
    """The TASP join of a link profile and a session shape: returns
    (party_order, chunk_order, note).  ``party_order`` is every party
    index, measured links slowest-first, unmeasured parties trailing in
    mesh order — the load-bearing half: the fan-out RPC to the slowest
    link's party is issued first.  ``chunk_order`` is a deterministic
    dispatch permutation derived from the same measurements via a
    round-robin ROUTE LABEL (slice j labeled to party ``j % n``): chunk
    sub-collectives move EVERY party's slice, so no chunk belongs to a
    link — on XLA's symmetric lowering the order is latency-neutral,
    and its value is being a pure auditable function of the profile
    that fronts the slices labeled to slow parties on runtimes that do
    schedule sub-collective transfers in dispatch order.  Reordering
    never changes bytes (asserted by the overlap-composition tests).
    ``note`` is the audit string the rpcz span records.  With no
    measured link both orders degenerate to mesh order — the
    pre-topology behavior."""
    n = len(party_ids)
    profile = profile or {}
    measured, unmeasured = [], []
    for i, pid in enumerate(party_ids):
        key = _profile_speed(profile.get(int(pid)))
        if key is None:
            unmeasured.append(i)
        else:
            measured.append((key, i))
    measured.sort()
    party_order = [i for _k, i in measured] + unmeasured
    # rank only MEASURED parties: with an empty profile the chunk sort
    # key is (inf, j) everywhere and the order stays mesh
    rank = {i: pos for pos, (_k, i) in enumerate(measured)}
    chunk_order = sorted(
        range(int(chunks)),
        key=lambda j: (rank.get(j % n, float("inf")), j),
    )
    if measured:
        gbps = {
            int(party_ids[i]): round(
                float(profile[int(party_ids[i])].get("gbps", 0.0) or 0.0), 4
            )
            for _k, i in measured
        }
        note = f"link_order={party_order} profile_gbps={gbps}"
        if chunks > 1:
            note += f" chunk_order={chunk_order}"
    else:
        note = ""
    return party_order, chunk_order, note


def _default_link_profile():
    """The scheduler's default telemetry source: this process's live
    device-link star (best-effort — a process with no links schedules
    in mesh order)."""
    try:
        from incubator_brpc_tpu.transport.device_link import link_profile

        return link_profile()
    except Exception:  # noqa: BLE001 — scheduling is advisory, never fatal
        return {}


def run_dispatch_session(
    party_ids: List[int],
    own_index: int,
    dm,
    operands: List[bytes],
    steps: int,
    service: str = "?",
    method: str = "?",
    should_abort: Optional[Callable[[], Optional[str]]] = None,
    session_id: Optional[str] = None,
    resume_from: int = 0,
    resume_state: Optional[Dict[int, Tuple[bytes, int]]] = None,
    checkpoint_every: int = 0,
    step_deadline_ms: float = 0.0,
    session_epoch: int = 0,
    chunks: int = 1,
    double_buffer: bool = False,
    quantize: str = "none",
    chunk_order=None,
    trace_id: int = 0,
    parent_span_id: int = 0,
) -> Tuple[np.ndarray, int, float]:
    """Run this party's side of a K-step session of ``dm``'s kernel;
    returns (own final row, own final n, elapsed seconds). Every party
    calls this with identical arguments except ``own_index`` — the jitted
    programs must match or the collectives cannot rendezvous. Each party
    device-places the shards it can ADDRESS: in the multi-controller
    deployment that is exactly its own row (the peers' devices are
    visible but not addressable — they contribute their shards from their
    own processes); in a single-controller run one call owns every shard
    and the session degenerates to the full computation. Operands stay
    device-resident across the chain: only the initial device_put and the
    final fetch cross the host boundary, and XLA pipelines the K
    dispatches (the ack/credit discipline is the response barrier the
    proposer collects — no per-step coordination).

    Elastic extensions: with ``checkpoint_every`` > 0 (and a session id)
    every C-th completed step's global arrays are retained in this
    party's device-resident ring; ``resume_from`` = R restores step R's
    state — from the local ring when retained, else from
    ``resume_state`` ({slot: (full-width row bytes, n)}, the reshard a
    replacement party is bootstrapped with) — and replays only steps
    > R; ``step_deadline_ms`` arms a watchdog that aborts the session
    fabric-wide when a SINGLE step (or the final fetch) stalls, instead
    of waiting out the whole session deadline.

    Overlap extensions (T3, docs/DEVICE_PLANE.md "the overlap
    scheduler"): ``chunks=C`` splits every step's operand on its leading
    axis into C independently-dispatched sub-collectives (the kernel
    must be registered ``chunkable`` and C must divide the width); each
    chunk is acked independently (a non-blocking readiness probe riding
    the step-ack discipline) and stamps its OWN watchdog progress, so a
    long overlapped step is never falsely aborted and an abort reason
    names step+chunk.  ``double_buffer=True`` keeps two step slots in
    flight: the ack of step k's chunk j is what (at the dataflow level)
    triggers step k+1's slice j — the host never blocks (zero host sync
    on the hot path; the device orders the chunk chain by dependency),
    whereas ``double_buffer=False`` with chunks inserts the serialized
    step-granularity ack barrier the A/B bench compares against.
    Checkpoints always capture WHOLE steps (the chunk slices re-concat
    before entering the ring), so a resume point is never a torn chunk.
    ``chunks=1, double_buffer=False`` is exactly the pre-overlap code
    path.

    Quantized extensions (parallel/quantized.py): ``quantize`` selects
    the kernel variant this chain binds — "none" runs ``dm`` itself,
    "int8"/"int4" resolve ``dm.quantized(mode)`` (no variant = clean
    ValueError before any dispatch); a quantized session also stores its
    checkpoint ring entries in the QUANTIZED representation (same ~4x as
    the wire), and the power-of-two scale discipline keeps resume replay
    byte-identical.  ``chunk_order`` is the topology-aware scheduler's
    session-uniform dispatch permutation over the chunk set (None = mesh
    order); chunk sub-collectives are independent, so the order never
    changes bytes — only which slice fronts the schedule."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    qdm = dm.quantized(quantize) if hasattr(dm, "quantized") else dm
    if qdm is None:
        raise ValueError(
            f"device method {service}.{method} has no {quantize} "
            "quantized variant"
        )
    dm = qdm
    quant_mode = getattr(dm, "quant_mode", "none") or "none"
    devices = _devices_by_id(party_ids)
    n = len(devices)
    if len(operands) != n:
        raise ValueError("one operand per party required")
    if not (0 <= resume_from <= steps):
        raise ValueError(f"resume_from {resume_from} outside 0..{steps}")
    chunks = _validate_chunks(dm, chunks, service, method)
    chunk_order = _validate_chunk_order(chunk_order, chunks)
    chunked = chunks > 1 or double_buffer
    mesh = Mesh(np.asarray(devices), ("par",))
    sharding = NamedSharding(mesh, P("par"))
    step_fn = _make_step(dm, mesh, sharding, party_ids)

    addressable = sharding.addressable_devices
    own_dev = devices[own_index]
    if own_dev not in addressable:
        raise ValueError(
            f"party {own_index} device {own_dev} is not addressable from "
            f"this process"
        )
    n_addr = sum(1 for d in devices if d in addressable)
    launch_order = _launch_order if n_addr > 1 else _no_launch_order
    ring = None
    if checkpoint_every and checkpoint_every > 0 and session_id:
        # a quantized session retains QUANTIZED ring entries: the per-
        # entry device cost drops from width float32-bytes per row to
        # the wire footprint — deep rings get the same ~4x the wire got
        row_cost = dm.wire_bytes() if quant_mode != "none" else dm.width
        ring = _checkpoint_ring(
            session_id, own_index, party_ids,
            entry_bytes=n_addr * (row_cost + 4),
        )
    ck_qz = None
    if ring is not None and quant_mode != "none":
        ck_qz = _make_ck_quant(mesh, sharding, dm, party_ids)

    def _ck_payload(rows):
        """What enters the ring: the raw row array, or (quantized
        session) its block-quantized twin — dispatched async like the
        chain itself, no host sync here either."""
        if ck_qz is None:
            return rows
        q_arr, e_arr = ck_qz(rows)
        return _QuantCk(q_arr, e_arr, quant_mode, dm.quant_block, dm.width)
    restored = None
    if resume_from > 0:
        restored = _restore_state(
            session_id, own_index, resume_from, devices, addressable,
            dm, resume_state,
        )
        if restored is None:
            raise LookupError(
                f"no checkpoint for session {session_id} step "
                f"{resume_from} reachable from party {own_index}"
            )
    row_shards, n_shards = [], []
    if restored is None:
        for i, dev in enumerate(devices):
            if dev not in addressable:
                continue
            row, nn = dm.pack(operands[i])
            row_shards.append(jax.device_put(row[None, :], dev))
            n_shards.append(
                jax.device_put(np.asarray([nn], dtype=np.int32), dev)
            )
    else:
        row_shards, n_shards = restored
    x = jax.make_array_from_single_device_arrays(
        (n, dm.width), sharding, row_shards
    )
    ns = jax.make_array_from_single_device_arrays((n,), sharding, n_shards)

    # the per-step watchdog: ``progress`` is (step index, last progress
    # instant, chunk index), advanced by the chain before every dispatch
    # and before the final fetch; a stall past the step deadline aborts
    # the session FABRIC-WIDE (abort_session → every local registrant's
    # event + the proposer's watcher sees the ESESSION answers), so one
    # wedged step costs the fabric a step deadline, not a session
    # deadline.  The wedged party itself still finishes its blocking
    # device call first — what the watchdog bounds is how long everyone
    # ELSE waits.  A CHUNKED step is C progress stamps, not one: each
    # sub-collective advances the stamp, so a long overlapped step is
    # never falsely aborted, a stall is attributed to step+chunk, and
    # with double-buffering a stalled last chunk of step k is named as
    # step k's — not misread as step k+1 hanging.
    # Dispatches are ASYNC (the host loop stamps per-step progress while
    # XLA pipelines the compute), so the final fetch is where the whole
    # replayed chain's device time is actually awaited: its allowance is
    # one step deadline PER replayed step, not one — a healthy long
    # session must not be aborted for merely computing.
    wd_stop = None
    progress = [resume_from, time.monotonic(), -1]
    # per-slice ack watermark: acked[j] = lowest step whose chunk-j ack
    # has NOT been observed yet; the fetch-phase abort reason names the
    # oldest unacked (step, chunk) so a wedged sub-collective is
    # attributed, not just "the fetch is slow"
    acked = [resume_from] * chunks
    if step_deadline_ms and step_deadline_ms > 0 and session_id:
        wd_stop = threading.Event()
        budget_s = step_deadline_ms / 1000.0
        fetch_allow_s = budget_s * max(1, steps - resume_from)

        def _watch_steps(sid=session_id, ep=session_epoch):
            poll = min(0.01, budget_s / 4)
            while not wd_stop.wait(poll):
                allowed = budget_s if progress[0] < steps else fetch_allow_s
                if time.monotonic() - progress[1] > allowed:
                    if progress[0] < steps:
                        what = f"step {progress[0]}"
                        if progress[2] >= 0:
                            what += f" chunk {progress[2]}/{chunks}"
                    else:
                        what = "final fetch"
                        oldest = min(acked)
                        if chunked and oldest < steps:
                            what += (
                                f" (oldest unacked step {oldest} chunk "
                                f"{acked.index(oldest)}/{chunks})"
                            )
                    abort_session(
                        sid,
                        f"{what} exceeded the {step_deadline_ms:g}ms "
                        "step deadline",
                        epoch=ep,
                    )
                    return

        threading.Thread(
            target=_watch_steps, name="mc-step-watchdog", daemon=True
        ).start()
    t0 = time.perf_counter()
    chunk_tally = 0  # sub-collectives dispatched (folded into bvars once)
    overlap_tally = 0  # dispatched while the same slice's predecessor flew
    pending_spans: List[list] = [[] for _ in range(chunks)]
    step_span = None
    try:
        if not chunked:
            for step_i in range(resume_from, steps):
                # fault plane: an aborted session exits the chain HERE,
                # between dispatches, with a clean ESESSION — dispatches
                # are async (XLA pipelines them), so the check costs
                # nothing and the party never enters a barrier its dead
                # peer cannot join.  Steps ALREADY dispatched when the
                # peer died stay queued on the device until the
                # runtime's own collective timeout; the host never waits
                # on them — every blocking wait below is abort-aware
                # (_await_or_abort), bounded by the abort broadcast,
                # every party's deadline watch and the per-step watchdog.
                if should_abort is not None:
                    why = should_abort()
                    if why:
                        raise SessionAborted(why)
                progress[0], progress[1] = step_i, time.monotonic()
                hook = _step_hook
                if hook is not None:
                    hook(step_i, own_index, 0)  # chaos-drill seam
                with launch_order:
                    # chained: operands stay on-device
                    x, ns = step_fn(x, ns)
                completed = step_i + 1
                if ring is not None and completed % checkpoint_every == 0:
                    # retaining the global arrays IS the checkpoint: the
                    # buffers stay device-resident, no host sync happens
                    # here, and the ring caps how many stay alive
                    ring.put(
                        completed, _ck_payload(x), ns,
                        int(get_flag("mc_dispatch_checkpoint_depth")),
                    )
        else:
            # -- the overlap scheduler: chunked sub-collectives ---------
            # the chunk sub-collective is step_fn itself applied to a
            # slice (jit re-specializes per shape; chunk-safety makes
            # the slice's bytes the slice of the full-width bytes)
            chunk_fn = step_fn
            concat_fn = None
            if chunks > 1:
                split_fn, concat_fn = _make_chunk_ops(
                    mesh, sharding, dm.width, chunks, party_ids
                )
                xs = list(split_fn(x))
            else:
                xs = [x]
            for step_i in range(resume_from, steps):
                step_span = _start_step_span(
                    service, method, step_i, steps, chunks, double_buffer,
                    trace_id, parent_span_id,
                )
                # chunk_order: the stamped topology-derived dispatch
                # permutation (independent sub-collectives: order
                # changes dispatch sequence, never bytes — see
                # schedule_session_order for its exact semantics)
                for j in chunk_order:
                    # the fault plane extends per-chunk: an abort lands
                    # BETWEEN sub-collectives, and the torn step (some
                    # chunks dispatched, others not) never checkpoints —
                    # a resume point is always a whole-step boundary
                    if should_abort is not None:
                        why = should_abort()
                        if why:
                            raise SessionAborted(why)
                    progress[0], progress[2] = step_i, j
                    progress[1] = time.monotonic()
                    hook = _step_hook
                    if hook is not None:
                        hook(step_i, own_index, j)  # chaos-drill seam
                    if double_buffer and step_i > resume_from:
                        # chunk-ack observation riding the step-ack
                        # discipline: xs[j] IS step k-1's chunk-j
                        # output.  Ready → the ack is observed (spans
                        # close, the watermark advances).  Not ready →
                        # the predecessor sub-collective is still in
                        # flight while the next slice dispatches: that
                        # IS the overlap, tallied.  Never blocks — the
                        # device orders the chain by dataflow, so the
                        # ack-gates-dispatch discipline holds on-device
                        # with zero host sync added.
                        if _chunk_ready(xs[j]):
                            acked[j] = step_i
                            _close_spans(pending_spans[j])
                        else:
                            overlap_tally += 1
                    # ns is NOT rethreaded through the chunk programs:
                    # the chunkable contract passes n through unchanged,
                    # so consuming a chunk's m output would only hand
                    # every slice of step k+1 a dataflow edge on step
                    # k's chunk-0 program — partially re-serializing the
                    # overlap the schedule exists to remove
                    with launch_order:
                        new_x, _ = chunk_fn(xs[j], ns)
                    xs[j] = new_x
                    chunk_tally += 1
                    csp = _start_chunk_span(
                        service, method, step_i, j, chunks, step_span,
                        trace_id, parent_span_id,
                    )
                    if csp is not None:
                        pending_spans[j].append(csp)
                completed = step_i + 1
                if not double_buffer:
                    # serialized schedule: the step-granularity ack
                    # barrier the overlap replaces — every chunk of this
                    # step observed complete before the next dispatches
                    # (the A/B baseline; a stalled chunk is named by its
                    # own progress stamp)
                    for j in chunk_order:
                        progress[0], progress[2] = step_i, j
                        progress[1] = time.monotonic()
                        _await_or_abort(xs[j], should_abort)
                        acked[j] = completed
                        _close_spans(pending_spans[j])
                if ring is not None and completed % checkpoint_every == 0:
                    # whole-step checkpoint: the chunk slices re-concat
                    # (async, device-resident) before entering the ring
                    # — a torn chunk can never become a resume point
                    x_ck = concat_fn(*xs) if chunks > 1 else xs[0]
                    ring.put(
                        completed, _ck_payload(x_ck), ns,
                        int(get_flag("mc_dispatch_checkpoint_depth")),
                    )
                if step_span is not None:
                    _end_session_span(step_span)
                    step_span = None
            x = concat_fn(*xs) if chunks > 1 else xs[0]
        if should_abort is not None:
            # last look before the blocking fetch: the final collect is
            # the one host-blocking point of the chain
            why = should_abort()
            if why:
                raise SessionAborted(why)
        progress[0], progress[2] = steps, -1
        progress[1] = time.monotonic()
        own_row = own_n = None
        _await_or_abort(x, should_abort)
        for s in x.addressable_shards:
            # a process can address several mesh devices (single-
            # controller runs): OUR shard is the one on devices[own_index]
            if s.device == own_dev:
                own_row = np.asarray(s.data).reshape(-1)
        for s in ns.addressable_shards:
            if s.device == own_dev:
                own_n = int(np.asarray(s.data).reshape(-1)[0])
        # the fetch materialized the whole chain: every outstanding chunk
        # ack is implied — close the remaining spans at their true ack
        # instant and settle the watermark
        for j in range(chunks):
            acked[j] = steps
            _close_spans(pending_spans[j])
    finally:
        if wd_stop is not None:
            wd_stop.set()
        if chunk_tally:
            dispatch_chunks << chunk_tally
        if overlap_tally:
            dispatch_overlapped_chunks << overlap_tally
        # an abort mid-step leaves spans open: close them as errored so
        # the trace shows the torn step instead of losing it
        from incubator_brpc_tpu.utils.status import ErrorCode as _EC

        for j in range(chunks):
            _close_spans(pending_spans[j], error_code=int(_EC.ESESSION))
        if step_span is not None:
            _end_session_span(step_span, error_code=int(_EC.ESESSION))
    elapsed = time.perf_counter() - t0
    assert own_row is not None and own_n is not None
    dispatch_sessions << 1
    dispatch_steps << (steps - resume_from)
    dispatch_session_us << elapsed * 1e6
    _method_counter(service, method) << 1
    if quant_mode != "none":
        # the quantization dividend, tallied once per session: bytes
        # the wire did NOT carry vs the exact float32 row at this
        # width, across every party and replayed step
        dispatch_quantized_sessions << 1
        saved = (dm.width - dm.wire_bytes()) * n * (steps - resume_from)
        if saved > 0:
            dispatch_bytes_saved << saved
    return own_row, own_n, elapsed


def _restore_state(
    session_id, own_index, step, devices, addressable, dm, resume_state
):
    """Rebuild this party's addressable shards of the session state at
    one checkpointed step: the local ring's device-resident buffers when
    retained (a survivor resuming in place — same devices, zero copies),
    falling back per-slot to ``resume_state`` rows shipped over the rpc
    plane (the replacement's bootstrap; also covers a survivor whose ring
    lost the slot).  Returns (row_shards, n_shards) or None when any
    addressable slot is unrecoverable."""
    import jax

    ring = _checkpoint_lookup(session_id, own_index) if session_id else None
    entry = ring.get(int(step)) if ring is not None else None
    payload, by_dev_n, old_pids = None, {}, ()
    if entry is not None:
        payload, old_ns = entry
        by_dev_n = {s.device: s for s in old_ns.addressable_shards}
        old_pids = ring.party_ids
    pay_devs = (
        [s.device for s in _payload_arrays(payload)[0].addressable_shards]
        if payload is not None
        else []
    )
    state = resume_state or {}
    row_shards, n_shards = [], []
    for i, dev in enumerate(devices):
        if dev not in addressable:
            continue
        src_dev = None
        if i < len(old_pids):
            src = [d for d in pay_devs if d.id == old_pids[i]]
            src_dev = src[0] if src else None
        if src_dev is not None and src_dev in by_dev_n:
            n_buf = by_dev_n[src_dev].data
            if isinstance(payload, _QuantCk):
                # quantized ring: the retained entry is the block-
                # quantized representation — dequantize on the host
                # (exact, power-of-two scales) and re-place.  The first
                # replayed step re-quantizes to the identical wire
                # bytes (idempotence), so the chain stays byte-
                # identical to the undisturbed run.
                row = payload.shard_row(src_dev)
                if row is None:
                    return None
                row_shards.append(jax.device_put(row.reshape(1, -1), dev))
                n_shards.append(jax.device_put(np.asarray(n_buf), dev))
                continue
            by_dev_row = {s.device: s for s in payload.addressable_shards}
            row_buf = by_dev_row[src_dev].data
            if src_dev != dev:
                # a replaced slot restored from a survivor's ring: the
                # retained buffer lives on the OLD device — move it
                row_buf = jax.device_put(np.asarray(row_buf), dev)
                n_buf = jax.device_put(np.asarray(n_buf), dev)
            row_shards.append(row_buf)
            n_shards.append(n_buf)
            continue
        if int(i) in state:
            row_bytes, nn = state[int(i)]
            try:
                row, n32 = dm.pack_state(row_bytes, nn)
            except ValueError:
                return None  # wrong-geometry reshard: unrecoverable slot
            row_shards.append(jax.device_put(row[None, :], dev))
            n_shards.append(
                jax.device_put(np.asarray([n32], dtype=np.int32), dev)
            )
            continue
        return None
    return row_shards, n_shards


# -- rpcz spans (annotated with method identity) -------------------------------


def _start_session_span(
    service: str,
    method: str,
    fingerprint: str,
    party_ids: List[int],
    own_index: int,
    steps: int,
    trace_id: int = 0,
    parent_span_id: int = 0,
    resume_from: int = 0,
    extra: str = "",
    forced: bool = False,
):
    from incubator_brpc_tpu.builtin.rpcz import (
        SPAN_TYPE_COLLECTIVE,
        start_custom_span,
    )

    span = start_custom_span(
        SPAN_TYPE_COLLECTIVE,
        service,
        method,
        trace_id=trace_id,
        parent_span_id=parent_span_id,
        forced=forced,
    )
    if span is not None:
        note = (
            f"method={service}.{method} fingerprint={fingerprint} "
            f"steps={steps} index={own_index} parties={party_ids}"
        )
        if resume_from > 0:
            # a resumed chain: the span shows how much work the
            # checkpoint saved (only steps > resume_from re-ran)
            note += f" resume_from={resume_from}"
        if extra:
            # quantize= / link-order audit trail (docs/OBSERVABILITY.md)
            note += " " + extra
        span.annotate(note)
    return span


def _end_session_span(span, error_code: int = 0) -> None:
    from incubator_brpc_tpu.builtin.rpcz import end_custom_span

    end_custom_span(span, error_code=error_code)


def _start_step_span(
    service, method, step_i, steps, chunks, double_buffer,
    trace_id, parent_span_id,
):
    """One step's COMPUTE span in an overlapped session: covers the host
    dispatch window of the step's sub-collectives; its children are the
    chunk spans, and a chunk span of step k that closes inside step
    k+1's window is the trace-level proof of overlap."""
    from incubator_brpc_tpu.builtin.rpcz import (
        SPAN_TYPE_COLLECTIVE,
        start_custom_span,
    )

    span = start_custom_span(
        SPAN_TYPE_COLLECTIVE, service, method,
        trace_id=trace_id, parent_span_id=parent_span_id,
    )
    if span is not None:
        span.annotate(
            f"compute step={step_i}/{steps} chunks={chunks} "
            f"schedule={'double_buffer' if double_buffer else 'serialized'}"
        )
    return span


def _start_chunk_span(
    service, method, step_i, j, chunks, step_span, trace_id, parent_span_id
):
    """One chunk sub-collective's span, nested inside its step's compute
    span (``chunk=<j>/<C>`` annotation schema, docs/OBSERVABILITY.md);
    ended at the chunk's ACK observation, so its interval is
    dispatch→ack — time-overlapping the next slice's compute span when
    the schedule actually overlaps."""
    from incubator_brpc_tpu.builtin.rpcz import (
        SPAN_TYPE_COLLECTIVE,
        start_custom_span,
    )

    span = start_custom_span(
        SPAN_TYPE_COLLECTIVE, service, method,
        trace_id=step_span.trace_id if step_span is not None else trace_id,
        parent_span_id=(
            step_span.span_id if step_span is not None else parent_span_id
        ),
    )
    if span is not None:
        span.annotate(f"chunk={j}/{chunks} step={step_i}")
    return span


def _close_spans(spans: list, error_code: int = 0) -> None:
    """End-and-drain a slice's pending chunk spans (ack observed, or the
    session tore down) — draining keeps a second close idempotent."""
    while spans:
        _end_session_span(spans.pop(0), error_code=error_code)


# -- server half ---------------------------------------------------------------


def _validate_proposal(req: dict):
    """Shared accept/run admission: bounds, then kernel identity. Returns
    (party_ids, own_index, steps, dm, err) where err is (code, text) on
    rejection — the clean control-stream reject that keeps a divergent
    party out of lockstep."""
    from incubator_brpc_tpu.utils.status import ErrorCode

    try:
        party_ids = [int(i) for i in req["parties"]]
        own_index = int(req["index"])
        steps = int(req["steps"])
        width = int(req["width"])
        service = str(req["service"])
        method = str(req["method"])
        fingerprint = str(req["fingerprint"])
        quantize = str(req.get("quantize", "") or "none")
    except (ValueError, KeyError, TypeError) as e:
        return None, None, None, None, (
            ErrorCode.EREQUEST, f"bad dispatch proposal: {e}"
        )
    if not (
        0 < steps <= MAX_STEPS
        and 0 < width <= MAX_WIDTH
        and 1 < len(party_ids) <= MAX_PARTIES
        and 0 <= own_index < len(party_ids)
        and len(set(party_ids)) == len(party_ids)
    ):
        return None, None, None, None, (
            ErrorCode.EREQUEST, "dispatch proposal out of bounds"
        )
    from incubator_brpc_tpu.parallel.quantized import QUANT_MODES

    if quantize not in QUANT_MODES:
        dispatch_rejects << 1
        return None, None, None, None, (
            ErrorCode.EREQUEST, f"unknown quantize mode {quantize!r}"
        )
    dm = resolve_method(service, method, width)
    if dm is None:
        dispatch_rejects << 1
        return None, None, None, None, (
            ErrorCode.ENOMETHOD,
            f"no device method {service}.{method} with width {width} "
            f"registered in this process",
        )
    dm = dm.quantized(quantize)
    if dm is None:
        # the session is quantized but this method registered no such
        # variant here — same class of divergence as a fingerprint
        # mismatch, same clean pre-lockstep reject
        dispatch_rejects << 1
        return None, None, None, None, (
            ErrorCode.EREQUEST,
            f"device method {service}.{method} has no {quantize} "
            f"quantized variant registered in this process",
        )
    ours = dm.fingerprint()
    if ours != fingerprint:
        # same name, different kernel: entering lockstep would run a
        # program the proposer never named — reject before any dispatch
        dispatch_rejects << 1
        return None, None, None, None, (
            ErrorCode.EREQUEST,
            f"device method fingerprint mismatch for {service}.{method}: "
            f"proposal {fingerprint} vs local {ours}",
        )
    try:
        _devices_by_id(party_ids)
    except ValueError as e:
        return None, None, None, None, (ErrorCode.EREQUEST, str(e))
    return party_ids, own_index, steps, dm, None


def make_dispatch_handler(server):
    """Server half of ``_tpu_transport.collective_dispatch``: validate a
    session proposal against the local registry (accept phase — nothing
    runs), or bind the resolved kernel and run this party's side of the
    lockstep chain (run phase), answering with the final shard.

    ``collective_max_concurrency`` admits RUNNING sessions here, not at
    the method gate: the abort broadcast, the resume census and the
    reshard fetches ride this same method and must reach a party whose
    one admitted session is the chain they act on."""
    run_limit = max(0, int(server.options.collective_max_concurrency))
    run_slots = threading.BoundedSemaphore(run_limit) if run_limit else None

    def collective_dispatch(cntl, request: bytes) -> bytes:
        try:
            req = json.loads(request.decode())
        except ValueError as e:
            from incubator_brpc_tpu.utils.status import ErrorCode

            cntl.set_failed(ErrorCode.EREQUEST, f"undecodable proposal: {e}")
            return b""
        if req.get("phase") == "abort":
            # the abort broadcast: validated as little as possible — a
            # survivor must unwedge even when the rest of the proposal
            # state is unreachable or corrupt
            sid = str(req.get("session_id", ""))
            try:
                # epoch-scoped: a straggler abort from a superseded run
                # must not kill the session's resumed run
                abort_epoch = (
                    int(req["epoch"]) if "epoch" in req else None
                )
            except (ValueError, TypeError):
                abort_epoch = None
            found = bool(sid) and abort_session(
                sid,
                str(req.get("reason", "")) or "aborted by proposer",
                epoch=abort_epoch,
            )
            return json.dumps({"aborted": found}).encode()
        if req.get("phase") == "resume_query":
            # the resume barrier's census: every LOCAL party's checkpoint
            # watermark for this session — the proposer min-joins these
            # over the survivors into the resume point
            sid = str(req.get("session_id", ""))
            wm = checkpoint_watermarks(sid) if sid else {}
            return json.dumps(
                {"watermarks": {str(k): v for k, v in wm.items()}}
            ).encode()
        if req.get("phase") == "fetch_shard":
            # reshard: materialize checkpointed rows for the requested
            # slots (the replacement party's bootstrap state)
            sid = str(req.get("session_id", ""))
            step = int(req.get("step", 0) or 0)
            slots = [int(s) for s in req.get("slots", ())]
            rows = checkpoint_fetch(sid, step, slots) if sid else {}
            return json.dumps(
                {"rows": {str(k): v for k, v in rows.items()}}
            ).encode()
        if req.get("phase") == "release":
            sid = str(req.get("session_id", ""))
            return json.dumps(
                {"released": bool(sid) and release_checkpoints(sid)}
            ).encode()
        party_ids, own_index, steps, dm, err = _validate_proposal(req)
        if err is not None:
            cntl.set_failed(*err)
            return b""
        service, method = str(req["service"]), str(req["method"])
        floor = int(get_flag("mc_dispatch_min_steps"))
        if req.get("phase") != "accept" and steps < floor:
            # the accept ack raised our target to the floor; a run
            # proposal below it means the proposer did not fold this
            # party's target — reject rather than silently dispatch a
            # count the accept never agreed to (the close-barrier echo
            # below only proves the VALIDATED count was run)
            from incubator_brpc_tpu.utils.status import ErrorCode

            dispatch_rejects << 1
            cntl.set_failed(
                ErrorCode.EREQUEST,
                f"run proposal steps {steps} below this party's accepted "
                f"floor {floor}",
            )
            return b""
        if req.get("phase") == "accept":
            # Nothing is run or reserved; ``target`` lets this party RAISE
            # the step count (mc_dispatch_min_steps — e.g. a pipeline-depth
            # floor). The proposer folds every target with max — the
            # 2-party close dance's max(targets) join, generalized to N.
            target = min(
                max(steps, int(get_flag("mc_dispatch_min_steps"))), MAX_STEPS
            )
            return json.dumps(
                {"accept": True, "index": own_index, "target": target}
            ).encode()
        try:
            operands = [
                base64.b64decode(op) for op in req.get("operands", [])
            ]
            if len(operands) != len(party_ids):
                raise ValueError("one operand per party required")
            for op in operands:
                if len(op) > dm.width:
                    raise ValueError(
                        f"operand of {len(op)}B exceeds width {dm.width}"
                    )
        except (ValueError, TypeError) as e:
            from incubator_brpc_tpu.utils.status import ErrorCode

            cntl.set_failed(ErrorCode.EREQUEST, f"bad operands: {e}")
            return b""
        # fault plane: a session_id-carrying run registers here so the
        # abort broadcast, the party's own deadline watch, link-death
        # feedback, and the proposer's control socket dying can all
        # unwedge this party mid-chain with a clean ESESSION
        session_id = str(req.get("session_id", "")) or None
        # elastic plane: the proposer stamps the checkpoint cadence and
        # step deadline into the run proposal (cadence MUST be uniform
        # across parties or the min-join loses its "last common step"
        # meaning); absent fields fall back to this party's own flags
        try:
            run_epoch = int(req.get("epoch", 0) or 0)
            resume_from = int(req.get("resume_from", 0) or 0)
            # overlap fields: the proposer stamps the chunk count and
            # schedule into the run proposal (session-uniform — every
            # party must dispatch the same sub-collective sequence or
            # the chunk collectives cannot rendezvous)
            chunks = _validate_chunks(
                dm, req.get("chunks", 1), service, method
            )
            chunk_order = _validate_chunk_order(
                req.get("chunk_order"), chunks
            )
            double_buffer = bool(req.get("double_buffer", False))
            if "checkpoint_every" in req:
                checkpoint_every = int(req["checkpoint_every"] or 0)
            else:
                checkpoint_every = int(get_flag("mc_dispatch_checkpoint_every"))
            if "step_deadline_ms" in req:
                step_deadline_ms = float(req["step_deadline_ms"] or 0)
            else:
                step_deadline_ms = float(
                    get_flag("mc_dispatch_step_deadline_ms")
                )
            resume_state = {
                int(k): (base64.b64decode(v["row"]), int(v["n"]))
                for k, v in (req.get("resume_state") or {}).items()
            }
            if not (0 <= resume_from <= steps):
                raise ValueError(f"resume_from {resume_from} out of bounds")
            if resume_from > 0 and session_id is None:
                raise ValueError("resume_from requires a session_id")
        except (ValueError, TypeError, KeyError) as e:
            from incubator_brpc_tpu.utils.status import ErrorCode

            dispatch_rejects << 1
            cntl.set_failed(ErrorCode.EREQUEST, f"bad run fields: {e}")
            return b""
        if run_slots is not None and not run_slots.acquire(blocking=False):
            from incubator_brpc_tpu.utils.status import ErrorCode

            cntl.set_failed(
                ErrorCode.ELIMIT,
                f"{run_limit} collective session(s) already running here",
            )
            return b""
        st = None
        sock_hook = None
        span = None
        try:
            if (
                session_id is not None
                and run_epoch <= aborted_epoch(session_id)
            ):
                # the abort for this epoch already passed through here: a
                # stale (reordered or retried) run proposal must not start a
                # zombie chain no peer will ever join
                from incubator_brpc_tpu.utils.status import ErrorCode

                cntl.set_failed(
                    ErrorCode.ESESSION,
                    f"session aborted: run epoch {run_epoch} already "
                    "tombstoned on this party",
                )
                return b""
            if session_id is not None:
                deadline_ms = float(req.get("deadline_ms", 0) or 0)
                if deadline_ms <= 0:
                    deadline_ms = float(
                        get_flag("mc_dispatch_session_deadline_ms")
                    )
                deadline = (
                    time.monotonic() + deadline_ms / 1000.0 if deadline_ms > 0
                    else 0.0
                )
                st = _register_session(
                    session_id, party_ids, deadline, owner=server,
                    epoch=run_epoch,
                )
                sock = getattr(cntl, "_sock", None)
                hooks = getattr(sock, "on_failed", None)
                if hooks is not None:
                    # the proposer died with us mid-chain: its control
                    # connection failing IS the death signal (socket feedback)
                    def _proposer_died(_s, _sid=session_id, _ep=run_epoch):
                        abort_session(
                            _sid, "proposer connection died mid-session",
                            epoch=_ep,
                        )

                    hooks.append(_proposer_died)
                    sock_hook = (hooks, _proposer_died)

            def _should_abort():
                if st.abort_event.is_set():
                    return st.abort_reason or "session aborted"
                if st.deadline and time.monotonic() > st.deadline:
                    abort_session(
                        st.session_id, "session deadline exceeded",
                        epoch=st.epoch,
                    )
                    return "session deadline exceeded"
                return None

            quant_note = ""
            if getattr(dm, "quant_mode", "none") != "none":
                quant_note = f"quantize={dm.quant_mode}"
            if chunk_order != list(range(chunks)):
                # the proposer's topology-derived route, auditable per party
                quant_note = (
                    quant_note + f" chunk_order={chunk_order}"
                ).strip()
            span = _start_session_span(
                service, method, dm.fingerprint(), party_ids, own_index, steps,
                trace_id=cntl.trace_id, parent_span_id=cntl.span_id,
                resume_from=resume_from, extra=quant_note,
                # the proposal rode in sampled (head-based): this party's
                # session span must not drop to a dry local bucket, or the
                # fleet-wide trace loses a whole party
                forced=bool(
                    getattr(cntl.request_meta, "sampled", 0)
                    if cntl.request_meta is not None
                    else 0
                ),
            )
            own_row, own_n, elapsed = run_dispatch_session(
                party_ids, own_index, dm, operands, steps,
                service=service, method=method,
                # no session state, nothing can abort it: the chain's
                # waits are then the plain blocking ones
                should_abort=_should_abort if st is not None else None,
                session_id=session_id, resume_from=resume_from,
                resume_state=resume_state,
                checkpoint_every=checkpoint_every,
                step_deadline_ms=step_deadline_ms,
                session_epoch=run_epoch,
                chunks=chunks, double_buffer=double_buffer,
                chunk_order=chunk_order,
                # step/chunk spans nest inside the session span (or the
                # proposing RPC's trace when the session span was not
                # sampled this time)
                trace_id=(
                    span.trace_id if span is not None else cntl.trace_id
                ),
                parent_span_id=(
                    span.span_id if span is not None else cntl.span_id
                ),
            )
        except SessionAborted as e:
            from incubator_brpc_tpu.utils.status import ErrorCode

            _end_session_span(span, error_code=ErrorCode.ESESSION)
            cntl.set_failed(ErrorCode.ESESSION, f"session aborted: {e.reason}")
            return b""
        except LookupError as e:
            # a resume proposal for a step this party no longer retains
            # (evicted ring, wrong process): a clean control-stream
            # reject — the proposer falls back to a full restart
            from incubator_brpc_tpu.utils.status import ErrorCode

            dispatch_rejects << 1
            _end_session_span(span, error_code=ErrorCode.EREQUEST)
            cntl.set_failed(ErrorCode.EREQUEST, f"cannot resume: {e}")
            return b""
        except Exception as e:
            dispatch_errors << 1
            from incubator_brpc_tpu.utils.status import ErrorCode

            _end_session_span(span, error_code=ErrorCode.EINTERNAL)
            logger.exception("dispatch session failed")
            cntl.set_failed(ErrorCode.EINTERNAL, f"dispatch session: {e!r}")
            return b""
        finally:
            if sock_hook is not None:
                try:
                    sock_hook[0].remove(sock_hook[1])
                except ValueError:
                    pass
            if st is not None:
                _unregister_session(st)
            if run_slots is not None:
                run_slots.release()
        _end_session_span(span)
        return json.dumps(
            {
                "result": base64.b64encode(
                    dm.unpack(own_row, own_n)
                ).decode(),
                "steps": steps,
                "resumed_from": resume_from,
                "elapsed_s": elapsed,
                "index": own_index,
            }
        ).encode()

    return collective_dispatch


# -- client half: the N-party session scheduler --------------------------------


def propose_dispatch(
    channels,
    party_ids: List[int],
    service: str,
    method: str,
    operands: List[bytes],
    steps: int = 1,
    proposer_index: Optional[int] = None,
    timeout_ms: float = 120000,
    session_deadline_ms: Optional[float] = None,
    session_id: Optional[str] = None,
    resume_from: int = 0,
    resume_state: Optional[Dict[int, Tuple[bytes, int]]] = None,
    resume_state_slots=None,
    checkpoint_every: Optional[int] = None,
    step_deadline_ms: Optional[float] = None,
    epoch: int = 0,
    chunks: int = 1,
    double_buffer: bool = False,
    quantize: str = "none",
    link_profile=None,
    chunk_order=None,
) -> dict:
    """Schedule an N-party session of a registered device method.

    ``chunks``/``double_buffer`` select the overlap schedule (T3): every
    step's operand splits into ``chunks`` independently-acked
    sub-collectives, and with ``double_buffer`` two step slots stay in
    flight (see :func:`run_dispatch_session`).  The proposer stamps both
    into the run proposal — the schedule is session-uniform, like the
    checkpoint cadence — and validates chunk-safety against its own
    registry before the accept fan-out.

    ``quantize`` ("none"/"int8"/"int4") binds the session to the named
    method's QUANTIZED variant (parallel/quantized.py): the proposal
    stamps the mode and the VARIANT's fingerprint, every party resolves
    the same variant locally and fingerprint-validates it at accept —
    exact vs quantized can never silently mix in one lockstep chain.
    A method with no such variant rejects cleanly before any fan-out.

    Topology awareness (TASP): the accept and run fan-outs are issued
    slowest-measured-link FIRST, and with ``chunks > 1`` the stamped
    ``chunk_order`` front-loads the slices owned by the slowest parties
    — both derived from ``link_profile`` ({device id: {"gbps",
    "rtt_us", ...}}, default this process's live DeviceLinkMap snapshot)
    and recorded in the rpcz session span so the chosen order is
    auditable.  Pass ``chunk_order`` explicitly to override the derived
    route (it must be a permutation of the chunk set).

    ``party_ids`` are global device ids in mesh order; ``operands[i]`` is
    party i's initial row. ``channels[j]`` is a host channel to the
    server playing the j-th REMOTE party index (every index except
    ``proposer_index``; with ``proposer_index=None`` the proposer is a
    pure scheduler and every party is remote — the ParallelChannel
    lowering's shape). Returns ``{"results": [bytes per party],
    "final_steps": k, "elapsed_s": proposer's chain seconds or None}``.

    Three phases over the star:
    1. accept fan-out + barrier — every party resolves the (service,
       method) pair locally and fingerprint-checks it; any reject
       surfaces HERE, before lockstep. ``final = max(all targets)``.
    2. run fan-out (async — every party must be dispatching before any
       can finish) under a fault watcher, then the proposer's own chain
       if it participates.
    3. completion barrier — every response must echo ``final`` (the
       convergent close: all parties dispatched exactly the same count).

    Fault semantics: the run phase registers a SESSION (random id +
    ``session_deadline_ms`` budget, default the RPC timeout) on every
    party.  The watcher classifies a failed run RPC: connectivity
    failures (dead party) and rejects both trigger an ABORT — an abort
    broadcast to every surviving party plus the local abort event — so
    every survivor exits its lockstep chain with ESESSION instead of
    hanging in a barrier; :class:`SessionAborted` then carries the dead
    and surviving index sets for the re-propose path
    (:func:`propose_with_recovery`).  Breaker feedback is charged to the
    dead party only: the survivors' ESESSION answers are excluded from
    error cost by the LB (lb/__init__._feed_breaker).
    """
    import threading as _threading

    n = len(party_ids)
    remote_indexes = [i for i in range(n) if i != proposer_index]
    if len(remote_indexes) != len(channels):
        raise ValueError("one channel per remote party required")
    if len(operands) != n:
        raise ValueError("one operand per party required")
    dm = resolve_method(service, method)
    if dm is None:
        raise LookupError(
            f"device method {service}.{method} not registered locally "
            f"(the proposer validates against its own registry too)"
        )
    quantize = (quantize or "none").strip() or "none"
    qdm = dm.quantized(quantize)
    if qdm is None:
        raise LookupError(
            f"device method {service}.{method} has no {quantize} "
            "quantized variant registered locally"
        )
    dm = qdm
    fingerprint = dm.fingerprint()
    for op in operands:
        if len(op) > dm.width:
            raise ValueError(
                f"operand of {len(op)}B exceeds method width {dm.width}"
            )
    chunks = _validate_chunks(dm, chunks, service, method)
    # topology-aware route: fan out slowest-measured-link first, and
    # front-load the chunk slices that cover the slowest parties (the
    # schedule is advisory for latency, load-bearing for audit — the
    # note below lands in the rpcz session span)
    if link_profile is None:
        link_profile = _default_link_profile()
    party_order, auto_chunk_order, sched_note = schedule_session_order(
        party_ids, link_profile, chunks
    )
    if chunk_order is None:
        chunk_order = auto_chunk_order
    else:
        chunk_order = _validate_chunk_order(chunk_order, chunks)
    sched_extra = (
        (f"quantize={quantize} " if quantize != "none" else "")
        + sched_note
    ).strip()

    # session identity + deadline: what the fault plane keys on.  Every
    # party gets the SAME budget, measured from its own clock at proposal
    # arrival — a partitioned party that never hears the abort broadcast
    # still unwedges at its own deadline.  A caller-supplied session_id
    # is a RESUME of that session: the parties' checkpoint rings are
    # keyed on it.
    import uuid

    if session_id is None:
        session_id = uuid.uuid4().hex
    sess_ms = (
        float(session_deadline_ms)
        if session_deadline_ms and session_deadline_ms > 0
        else float(get_flag("mc_dispatch_session_deadline_ms"))
        or float(timeout_ms)
    )
    ckpt_every = (
        int(checkpoint_every)
        if checkpoint_every is not None
        else int(get_flag("mc_dispatch_checkpoint_every"))
    )
    step_ms = (
        float(step_deadline_ms)
        if step_deadline_ms is not None
        else float(get_flag("mc_dispatch_step_deadline_ms"))
    )
    resume_from = int(resume_from or 0)
    if not (0 <= resume_from <= steps):
        raise ValueError(f"resume_from {resume_from} outside 0..{steps}")

    def proposal(idx: int, nsteps: int, phase: str = "") -> bytes:
        d = {
            "parties": party_ids,
            "index": idx,
            "steps": nsteps,
            "width": dm.width,
            "service": service,
            "method": method,
            "fingerprint": fingerprint,
        }
        if quantize != "none":
            # session-uniform, validated at accept AND run: the
            # fingerprint above IS the quantized variant's, so a party
            # missing the variant (or holding a different one) rejects
            # before lockstep like any other kernel divergence
            d["quantize"] = quantize
        if phase:
            d["phase"] = phase
        else:
            # the FULL operand list: each party device-places only the
            # shards it can address (its own, in the mc deployment), but
            # a single-controller party owns every shard and needs them
            d["operands"] = [
                base64.b64encode(op).decode() for op in operands
            ]
            d["session_id"] = session_id
            d["deadline_ms"] = sess_ms
            d["epoch"] = int(epoch)
            # elastic plane: the proposer owns the cadence (uniform
            # across parties — the min-join's "last common step" depends
            # on it) and the step watchdog; a resumed run names the
            # agreed restore point plus bootstrap rows for parties
            # without a ring (the replacement)
            d["checkpoint_every"] = ckpt_every
            d["step_deadline_ms"] = step_ms
            # the overlap schedule is session-uniform: every party must
            # dispatch the same chunk sequence or the sub-collectives
            # cannot rendezvous
            if chunks > 1:
                d["chunks"] = chunks
                if chunk_order != list(range(chunks)):
                    # the topology-derived route rides the run proposal
                    # (session-uniform: every party must dispatch the
                    # same sub-collective sequence to rendezvous)
                    d["chunk_order"] = chunk_order
            if double_buffer:
                d["double_buffer"] = True
            if resume_from > 0:
                d["resume_from"] = resume_from
                # bootstrap rows ride only to the parties that need them
                # (resume_state_slots — the replacements; survivors
                # restore from their own rings): shipping the full state
                # to every party would be N^2 x width control bytes
                if resume_state and (
                    resume_state_slots is None or idx in resume_state_slots
                ):
                    d["resume_state"] = {
                        str(i): {
                            "row": base64.b64encode(bytes(row)).decode(),
                            "n": int(nn),
                        }
                        for i, (row, nn) in resume_state.items()
                    }
        return json.dumps(d).encode()

    # fleet-wide trace: the proposer's ambient trace context (the RPC
    # handler this proposal runs inside, if any) or a fresh trace id
    # rides EVERY control RPC of this session, so each party's handler
    # span + session/step/chunk spans join one cross-process trace —
    # `rpc_view --trace <id> --targets ...` assembles it.  The sampled
    # bit propagates the head-based decision: sessions are heavyweight
    # (one proposal, N parties), so a proposer with rpcz on samples its
    # sessions at the edge and every party honors that.
    from incubator_brpc_tpu.builtin.rpcz import (
        _new_id as _new_trace_id,
        current_trace_context,
        rpcz_enabled,
    )

    amb_trace, amb_parent = current_trace_context()
    session_trace = amb_trace or (_new_trace_id() if rpcz_enabled() else 0)
    session_sampled = 1 if session_trace else 0
    fleet_trace = (session_trace, amb_parent, session_sampled)

    def _call(ch, payload):
        # scheduling rides the host plane — the shared control-call shape
        return _control_call(ch, payload, timeout_ms, trace=fleet_trace)

    # fan-out order: slowest measured link FIRST (TASP) — that party's
    # accept/run RPC needs the longest lead before each barrier; parties
    # with no telemetry keep mesh order at the tail.  The channel list
    # itself stays positional (callers index it by remote slot).
    fan = sorted(
        zip(channels, remote_indexes),
        key=lambda p: party_order.index(p[1]),
    )

    # Phase 1 — accept barrier + the monotone-max step-count join
    accepts = [
        _call(ch, proposal(idx, steps, phase="accept")) for ch, idx in fan
    ]
    deadline = time.monotonic() + timeout_ms / 1000.0
    final = steps
    for cntl, ev in accepts:
        if not ev.wait(max(0.0, deadline - time.monotonic())):
            raise TimeoutError("dispatch peer never acknowledged proposal")
        if cntl.failed():
            raise RuntimeError(
                f"dispatch proposal rejected: {cntl.error_text}"
            )
        ack = json.loads(cntl.response_payload.decode())
        final = max(final, int(ack.get("target", steps)))

    # Phase 2 — run fan-out (async: a sync proposal would deadlock — the
    # first party's collective blocks on parties never told to start),
    # in the same slowest-first order as the accept fan-out
    pending = [_call(ch, proposal(idx, final)) for ch, idx in fan]
    fan_indexes = [idx for _ch, idx in fan]
    from incubator_brpc_tpu.utils.status import ErrorCode

    # connectivity-class failures of a RUN rpc = the party is DEAD for
    # this session (its chain will never converge); anything else is a
    # reject.  Both abort the session — only death feeds the re-propose
    # path's survivor set.
    _DEATH_CODES = frozenset(
        {
            ErrorCode.EFAILEDSOCKET, ErrorCode.EEOF, ErrorCode.ECLOSE,
            ErrorCode.EHOSTDOWN, ErrorCode.ERPCTIMEDOUT, ErrorCode.ELOGOFF,
            ErrorCode.ETIMEDOUT,
        }
    )
    session_deadline = time.monotonic() + sess_ms / 1000.0
    st = _register_session(
        session_id, party_ids, session_deadline, epoch=epoch
    )
    outcome = {"dead": [], "rejects": [], "reason": ""}
    watch_stop = _threading.Event()

    def _broadcast_abort(reason: str, skip) -> None:
        """phase:"abort" to every party not already known dead (async,
        best-effort — each party's own deadline is the backstop)."""
        msg = json.dumps(
            {
                "phase": "abort",
                "session_id": session_id,
                "reason": reason,
                "epoch": int(epoch),
            }
        ).encode()
        for ch, idx in fan:
            if idx in skip:
                continue
            try:
                _call(ch, msg)
            except Exception:
                logger.exception("abort broadcast to party %d failed", idx)

    broadcast_done = [False]

    def _trigger_abort(reason: str) -> None:
        outcome["reason"] = outcome["reason"] or reason
        if not broadcast_done[0]:
            # one broadcast per session: later classifications (a second
            # death found while the first abort settles) add to the
            # outcome but the survivors were already told
            broadcast_done[0] = True
            _broadcast_abort(reason, set(outcome["dead"]))
        abort_session(session_id, reason, epoch=epoch)

    def _watch() -> None:
        # the generalized rejection watch (supersedes the old fixed-50 ms
        # participating-proposer scan): classify every settled run RPC as
        # it lands; on the FIRST death/reject — or the session deadline —
        # abort fabric-wide so survivors (the proposer's own chain
        # included) exit their lockstep loops instead of waiting in a
        # barrier the dead party can never join.  After an abort the
        # watcher KEEPS scanning until every run RPC settles (or the
        # deadline): an ESESSION answer is a SURVIVOR reporting the abort
        # (its link saw the death first, or our broadcast arrived) — not
        # a reject, and never the dead party, which must still be
        # identified for the re-propose path.
        seen = set()
        while not watch_stop.wait(0.01):
            done = True
            now = time.monotonic()
            for (cntl, ev), idx in zip(pending, fan_indexes):
                if not ev.is_set():
                    done = False
                    continue
                if idx in seen or not cntl.failed():
                    continue
                seen.add(idx)
                code = cntl.error_code
                if code == ErrorCode.ESESSION:
                    # cooperative abort report from a LIVING party:
                    # propagate (covers the link-death-detected-remotely
                    # ordering) but blame nobody
                    _trigger_abort(
                        f"party {idx} reported abort: {cntl.error_text}"
                    )
                elif code in _DEATH_CODES:
                    outcome["dead"].append(idx)
                    _trigger_abort(
                        f"party {idx} died mid-session: {cntl.error_text}"
                    )
                else:
                    outcome["rejects"].append((idx, cntl.error_text))
                    _trigger_abort(
                        f"party {idx} rejected the run: {cntl.error_text}"
                    )
            if done:
                return
            if st.abort_event.is_set() and not broadcast_done[0]:
                # aborted from OUTSIDE the rpc plane (the proposer's own
                # link-death hook fired): the survivors still need the
                # broadcast — their links may be fine
                _trigger_abort(st.abort_reason or "session aborted")
            if now > session_deadline:
                _trigger_abort("session deadline exceeded")
                return

    watcher = _threading.Thread(
        target=_watch, name="mc-session-watch", daemon=True
    )
    watcher.start()

    own_elapsed = None
    results: List[Optional[bytes]] = [None] * n
    abort_exc: Optional[SessionAborted] = None
    sched_span = None
    if proposer_index is None and sched_extra:
        # a pure scheduler leaves the audit span too: the quantize mode,
        # chosen link order and the profile it came from must be
        # traceable even when the proposer runs no chain of its own
        # (index=-1 marks the scheduler role)
        sched_span = _start_session_span(
            service, method, fingerprint, party_ids, -1, final,
            trace_id=session_trace, parent_span_id=amb_parent,
            resume_from=resume_from, extra=sched_extra,
            forced=bool(session_sampled),
        )
    try:
        if proposer_index is not None:

            def _own_should_abort():
                if st.abort_event.is_set():
                    return st.abort_reason or "session aborted"
                if time.monotonic() > session_deadline:
                    abort_session(
                        session_id, "session deadline exceeded", epoch=epoch
                    )
                    return "session deadline exceeded"
                return None

            span = _start_session_span(
                service, method, fingerprint, party_ids, proposer_index,
                final, trace_id=session_trace, parent_span_id=amb_parent,
                resume_from=resume_from, extra=sched_extra,
                forced=bool(session_sampled),
            )
            try:
                own_row, own_n, own_elapsed = run_dispatch_session(
                    party_ids, proposer_index, dm, operands,
                    final, service=service, method=method,
                    should_abort=_own_should_abort,
                    session_id=session_id, resume_from=resume_from,
                    resume_state=resume_state,
                    checkpoint_every=ckpt_every, step_deadline_ms=step_ms,
                    session_epoch=epoch,
                    chunks=chunks, double_buffer=double_buffer,
                    chunk_order=chunk_order,
                    trace_id=(
                        span.trace_id if span is not None else session_trace
                    ),
                    parent_span_id=(
                        span.span_id if span is not None else amb_parent
                    ),
                )
            except SessionAborted as e:
                _end_session_span(span, error_code=ErrorCode.ESESSION)
                abort_exc = e
            except Exception:
                dispatch_errors << 1
                _end_session_span(span, error_code=ErrorCode.EINTERNAL)
                # our own chain failed: the peers' chains can never
                # converge either — take the whole session down cleanly
                _trigger_abort("proposer chain failed")
                raise
            else:
                _end_session_span(span)
                results[proposer_index] = dm.unpack(own_row, own_n)

        # Phase 3 — completion barrier; the watcher exits once every run
        # RPC settled, or as soon as it aborted the session
        watcher.join()
        if st.abort_event.is_set() or abort_exc is not None:
            dead = sorted(set(outcome["dead"]))
            survivors = [i for i in range(n) if i not in set(dead)]
            reason = (
                outcome["reason"]
                or (abort_exc.reason if abort_exc is not None else "")
                or st.abort_reason
                or "session aborted"
            )
            raise SessionAborted(
                reason,
                dead_indexes=dead,
                survivor_indexes=survivors,
                rejects=outcome["rejects"],
                session_id=session_id,
                final_steps=final,
            )
        for (cntl, ev), idx in zip(pending, fan_indexes):
            if cntl.failed():  # defensive: the watcher classifies these
                raise RuntimeError(
                    f"dispatch peer failed: {cntl.error_text}"
                )
            resp = json.loads(cntl.response_payload.decode())
            # each party echoes the count it validated AND ran (a proposal
            # below the party's accepted floor is rejected, never silently
            # re-counted) — a mismatch here means a corrupted or stale
            # proposal reached that party
            if int(resp.get("steps", -1)) != final:
                raise RuntimeError(
                    f"party {idx} dispatched {resp.get('steps')} steps, "
                    f"agreed final was {final} — close did not converge"
                )
            results[idx] = base64.b64decode(resp["result"])
        # clean completion: nothing left to resume — release every
        # party's checkpoint ring (best-effort broadcast; the eviction
        # cap is the backstop for a proposer that dies before this)
        if ckpt_every > 0:
            release_checkpoints(session_id)
            msg = json.dumps(
                {"phase": "release", "session_id": session_id}
            ).encode()
            for ch in channels:
                try:
                    _call(ch, msg)
                except Exception:
                    logger.exception("checkpoint release broadcast failed")
    finally:
        watch_stop.set()
        _unregister_session(st)
        if sched_span is not None:
            _end_session_span(
                sched_span,
                error_code=(
                    int(ErrorCode.ESESSION)
                    if (st.abort_event.is_set() or abort_exc is not None)
                    else 0
                ),
            )
    return {
        "results": results,
        "final_steps": final,
        "elapsed_s": own_elapsed,
        "session_id": session_id,
        "resumed_from": resume_from if resume_from > 0 else None,
        "quantize": quantize,
        # the proposer-side wire accounting the dryrun gate and bench
        # compare: bytes every party put on the party axis across the
        # REPLAYED steps (exact rows ship dm.width per party per step;
        # a resumed run only moved steps past the checkpoint — same
        # basis as mc_dispatch_bytes_saved)
        "wire_bytes": dm.wire_bytes() * n * (final - resume_from),
        "link_order": party_order,
        "chunk_order": chunk_order,
    }


def _control_call(ch, payload: bytes, timeout_ms: float, trace=None):
    """One control-stream RPC (resume barrier traffic rides the same
    host-plane method the proposals do).  ``trace`` is the proposer's
    ``(trace_id, parent_span_id, sampled)`` fleet-trace context: stamped
    on the controller so the proposal crosses the wire inside the
    proposer's trace and every party's spans join it."""
    import threading as _threading

    from incubator_brpc_tpu.rpc.controller import Controller
    from incubator_brpc_tpu.transport.device_link import HANDSHAKE_SERVICE

    cntl = Controller(timeout_ms=timeout_ms)
    cntl._force_host = True
    if trace is not None and trace[0]:
        cntl.trace_id = int(trace[0])
        cntl.parent_span_id = int(trace[1])
        cntl.trace_sampled = 1 if trace[2] else 0
    ev = _threading.Event()
    ch.call_method(
        HANDSHAKE_SERVICE, DISPATCH_METHOD, payload, cntl=cntl,
        done=lambda c, _ev=ev: _ev.set(),
    )
    return cntl, ev


def _query_watermarks(
    session_id: str, survivor_pairs, timeout_ms: float
) -> Dict[int, dict]:
    """The resume barrier's gather half: ask every surviving remote party
    for its checkpoint census (phase:"resume_query"), merge with the
    proposer-local census (a participating proposer — and, in-process,
    co-hosted parties — answer from the same registry).  A survivor that
    fails the query contributes nothing, which drags the min-join to 0 —
    the safe side."""
    msg = json.dumps(
        {"phase": "resume_query", "session_id": session_id}
    ).encode()
    calls = []
    for ch, idx in survivor_pairs:
        try:
            calls.append(_control_call(ch, msg, timeout_ms))
        except Exception:
            logger.exception("resume query to party %d failed", idx)
    merged: Dict[int, dict] = dict(checkpoint_watermarks(session_id))
    deadline = time.monotonic() + timeout_ms / 1000.0
    for cntl, ev in calls:
        if not ev.wait(max(0.0, deadline - time.monotonic())):
            continue
        if cntl.failed():
            continue
        try:
            ans = json.loads(cntl.response_payload.decode())
            for k, info in (ans.get("watermarks") or {}).items():
                slot = int(k)
                have = merged.get(slot)
                if have is None or int(info.get("watermark", 0)) > int(
                    have.get("watermark", 0)
                ):
                    merged[slot] = info
        except (ValueError, TypeError, AttributeError):
            continue
    return merged


def _fetch_state(
    session_id: str,
    step: int,
    slots: List[int],
    channels,
    timeout_ms: float,
    required=None,
) -> Optional[Dict[int, Tuple[bytes, int]]]:
    """Reshard: assemble session state at one checkpointed step from the
    survivors' rings (local first, then phase:"fetch_shard" over the rpc
    plane) — what bootstraps a replacement party.  Returns
    {slot: (full-width row bytes, n)} covering whatever was reachable,
    or None when a REQUIRED slot (default: all of ``slots``) is
    unrecoverable — the caller then falls back to a full restart.  On a
    true multi-controller fabric each survivor serves only its own slot,
    so asking for every slot with ``required`` = the replaced ones gets
    the replacement everything reachable without failing the resume on
    rows nobody can provide."""
    # local rings first, raw (no b64 round trip for rows already here)
    state: Dict[int, Tuple[bytes, int]] = dict(
        _checkpoint_rows(session_id, step, slots)
    )

    def _absorb(rows: Dict) -> None:
        for k, v in rows.items():
            slot = int(k)
            if slot not in state:
                state[slot] = (base64.b64decode(v["row"]), int(v["n"]))

    for ch in channels:
        missing = [s for s in slots if s not in state]
        if not missing:
            break
        msg = json.dumps(
            {
                "phase": "fetch_shard",
                "session_id": session_id,
                "step": int(step),
                "slots": missing,
            }
        ).encode()
        try:
            cntl, ev = _control_call(ch, msg, timeout_ms)
        except Exception:
            logger.exception("shard fetch failed")
            continue
        if not ev.wait(timeout_ms / 1000.0) or cntl.failed():
            continue
        try:
            _absorb(
                json.loads(cntl.response_payload.decode()).get("rows") or {}
            )
        except (ValueError, TypeError, KeyError, AttributeError):
            continue
    need = slots if required is None else required
    if any(s not in state for s in need):
        return None
    return state


def propose_with_recovery(
    channels,
    party_ids: List[int],
    service: str,
    method: str,
    operands: List[bytes],
    steps: int = 1,
    proposer_index: Optional[int] = None,
    timeout_ms: float = 120000,
    session_deadline_ms: Optional[float] = None,
    max_reproposals: int = 1,
    spares=None,
    checkpoint_every: Optional[int] = None,
    step_deadline_ms: Optional[float] = None,
    chunks: int = 1,
    double_buffer: bool = False,
    quantize: str = "none",
    link_profile=None,
) -> dict:
    """:func:`propose_dispatch` with the elastic recovery path: a session
    that aborts on PARTY DEATH heals instead of restarting from nothing
    (up to ``max_reproposals`` times).  Two recovery modes, tried in
    order:

    1. **Resume with replacement** — when ``spares`` (a list of
       ``(channel, device_id)`` standby parties) can fill every dead
       slot: the resume barrier min-joins the survivors' checkpoint
       watermarks into the last COMMON checkpointed step, the dead
       party's state is re-sharded out of the survivors' rings over the
       rpc plane, and the SAME session (same id, same party-set width,
       same agreed step count) re-runs only the steps past the resume
       point — byte-identical to an undisturbed run.  Zero common
       checkpoint falls back to a full restart, still over the healed
       party set.
    2. **Shrink restart** — no spare: the PR-8 path, a fresh session
       from step 0 over the survivors only (an axis-reducing kernel
       cannot RESUME with fewer parties — re-running checkpointed-past
       steps with a divergent party set is exactly what the fabricverify
       resume model forbids).

    Rejects and proposer death are not recoverable and re-raise.  The
    result dict gains ``dead_party_ids``, ``replaced_party_ids`` and
    ``resumed_from`` (None unless the winning run was a resume)."""
    chs = list(channels)
    pids = [int(p) for p in party_ids]
    ops = list(operands)
    pidx = proposer_index
    dropped: List[int] = []
    replaced: List[int] = []
    spare_pool = list(spares or ())
    import uuid

    session_id = uuid.uuid4().hex
    run_steps = steps
    resume_from = 0
    resume_state: Optional[Dict[int, Tuple[bytes, int]]] = None
    resumed = False
    for attempt in range(max_reproposals + 1):
        remote = [i for i in range(len(pids)) if i != pidx]
        try:
            out = propose_dispatch(
                chs, pids, service, method, ops, steps=run_steps,
                proposer_index=pidx, timeout_ms=timeout_ms,
                session_deadline_ms=session_deadline_ms,
                session_id=session_id, resume_from=resume_from,
                resume_state=resume_state,
                resume_state_slots=frozenset(
                    i for i in range(len(pids))
                    if pids[i] in set(replaced)
                ) or None,
                checkpoint_every=checkpoint_every,
                step_deadline_ms=step_deadline_ms,
                epoch=attempt,
                chunks=chunks, double_buffer=double_buffer,
                quantize=quantize, link_profile=link_profile,
            )
            out["dead_party_ids"] = dropped
            out["replaced_party_ids"] = replaced
            if resumed:
                dispatch_resumes << 1
            return out
        except SessionAborted as e:
            dead = set(e.dead_indexes)
            if (
                attempt == max_reproposals
                or not dead
                or e.rejects
                or (pidx is not None and pidx in dead)
            ):
                raise
            have_spares = len(spare_pool) >= len(dead)
            if not have_spares and len(pids) - len(dead) < 2:
                # a shrink below 2 parties is no session; replacement
                # does not shrink, so the width guard only gates mode 2
                raise
            run_steps = max(run_steps, e.final_steps or 0)
            if have_spares:
                # elastic heal: replacement + resume (mode 1)
                dropped.extend(pids[i] for i in sorted(dead))
                survivor_slots = [
                    i for i in range(len(pids)) if i not in dead
                ]
                surv_pairs = [
                    (ch, idx)
                    for ch, idx in zip(chs, remote)
                    if idx not in dead
                ]
                wms = _query_watermarks(session_id, surv_pairs, timeout_ms)
                point = resume_point(
                    {i: wms.get(i) for i in survivor_slots}
                )
                for slot in sorted(dead):
                    sch, sdev = spare_pool.pop(0)
                    replaced.append(int(sdev))
                    pids[slot] = int(sdev)
                    chs[remote.index(slot)] = sch
                state = None
                if point > 0:
                    # reshard for the REPLACEMENTS: gather every slot
                    # reachable at the resume point (a single-controller
                    # replacement addresses all slots; a true
                    # multi-controller one only its own), but REQUIRE
                    # only the replaced slots — survivors restore their
                    # slots from their own rings, and the bootstrap rows
                    # ride only to the replacement parties
                    # (resume_state_slots below).  A dead slot no
                    # reachable ring covers (a true mc fabric, where the
                    # dead party's ring died with it) forces the
                    # full-restart fallback — still over the healed set.
                    state = _fetch_state(
                        session_id, point, list(range(len(pids))),
                        [ch for ch, _i in surv_pairs], timeout_ms,
                        required=sorted(dead),
                    )
                    if state is None:
                        point = 0  # reshard incomplete: full restart
                resume_from = point
                resume_state = state
                resumed = True
                dispatch_replaced_parties << len(dead)
                logger.warning(
                    "resuming %s.%s session %s from step %d with %d "
                    "replacement(s) after: %s",
                    service, method, session_id, point, len(dead),
                    e.reason,
                )
            else:
                # shrink restart (mode 2): new session over the
                # survivors; the old session's rings are released
                # best-effort (the eviction cap is the backstop)
                dropped.extend(pids[i] for i in sorted(dead))
                logger.warning(
                    "re-proposing %s.%s over %d survivor(s) after: %s",
                    service, method, len(pids) - len(dead), e.reason,
                )
                release_checkpoints(session_id)
                rel = json.dumps(
                    {"phase": "release", "session_id": session_id}
                ).encode()
                keep = [i for i in range(len(pids)) if i not in dead]
                chs = [
                    ch for ch, idx in zip(chs, remote) if idx not in dead
                ]
                for ch in chs:
                    try:
                        _control_call(ch, rel, timeout_ms)
                    except Exception:
                        logger.exception("checkpoint release failed")
                ops = [ops[i] for i in keep]
                pids = [pids[i] for i in keep]
                if pidx is not None:
                    pidx = keep.index(pidx)
                session_id = uuid.uuid4().hex
                resume_from = 0
                resume_state = None
                resumed = False
    raise AssertionError("unreachable")


# -- the ParallelChannel lowering ----------------------------------------------


def lower_parallel_call(
    channels,
    devices,
    service: str,
    method: str,
    requests: List[bytes],
    timeout_ms: float,
) -> List[bytes]:
    """One combo call lowered onto the method plane: the sub-channels'
    server devices form the party axis (channel order — the same order
    the single-controller fused dispatch stacks, so merges are
    byte-identical), each party's operand is its sub-request, the
    proposer is a pure scheduler (its process cannot address any party
    device), and one 1-step session replaces the host fan-out. Returns
    per-sub response bytes in channel order.

    Resume is transparent here: the call routes through
    :func:`propose_with_recovery`, so a multi-step lowering (or a future
    combo batching several steps into one session) heals the same way a
    direct session does.  A 1-step session has no checkpointed past and
    no spare pool, so an abort still surfaces as :class:`SessionAborted`
    and the combo layer falls back to the host fan-out — unchanged
    semantics, one recovery plane."""
    if not timeout_ms or timeout_ms <= 0:
        timeout_ms = 120000.0
    out = propose_with_recovery(
        channels,
        [d.id for d in devices],
        service,
        method,
        requests,
        steps=1,
        proposer_index=None,
        timeout_ms=timeout_ms,
        max_reproposals=0,
    )
    return out["results"]
