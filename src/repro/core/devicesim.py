"""Device-resident batched simulation of the homogeneous fixed-chunk regime.

``core/fastpath.py`` collapses the virtual-time event loop into a max-plus
recurrence per round — but it is still ONE simulation per Python call, and
the adaptive portfolio sweep / resilience grids need THOUSANDS of them
(candidate × perturbation draw).  This module ports the recurrence to JAX
and batches whole sweeps into one ``jit``-compiled ``vmap`` call:

  * the ROUND phase is a ``lax.scan`` over assignment rounds carrying
    (arrival times, in-flight chunks, liveness): per round one
    ``lax.cummax`` computes every master end-time
    ``M_w = max(A_w, M_{w-1}) + h`` and a cumulative-sum over the
    assignment mask hands out the next chunks in serve order.  Unlike
    fastpath, deaths are handled in-recurrence: a worker whose chunk
    completion falls at-or-after its fail-stop instant drops out holding
    the chunk (the chunk is LOST, exactly as in ``Engine.run``);
  * the no-failure TAIL (last in-flight round, final partial chunks, the
    rDLB end-of-loop duplicates) is closed-form: one more cummax round,
    a sorted cummax over the remainder reports, and an O(remainder)
    micro-loop reproducing the re-issue ring pointer;
  * the FAILURE tail runs an exact transaction-phase ``lax.scan``: each
    step serves the earliest pending arrival (argmin = the event heap),
    reproducing report/commit/first-completion-wins, the re-issue ring's
    oldest-first rotating pointer, duplicate-slot leaks on dup-holder
    death, and the non-robust Fig.-1b hang (``t_par = inf``).

Everything runs in float64 (``jax.enable_x64`` scoped to the device calls
only, so the rest of the process keeps JAX's f32 default)
and is vmapped over a leading (candidate × draw) axis.  Static scan
budgets are computed host-side from the batch's worst case; an element
that exhausts its budget comes back with ``valid=False`` and the caller
MUST re-run it on the scalar engine — the device path degrades to the
oracle, never silently mis-simulates.

Parity boundary (asserted in tests/test_devicesim.py): within the
lowered regime — virtual mode, fixed-chunk technique (SS / STATIC /
mFSC / FSC), homogeneous alive workers, uncapped duplicates,
(near-)uniform task costs, ``h > 0`` — ``t_par``, chunk/duplicate/waste
counts and per-worker accounting match ``Engine.run`` to float64
round-off.  Anything else (``lower_run`` returns a reason string)
declines and runs the scalar loop unchanged.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import dls

_BIG = np.int32(2 ** 30)        # "no chunk" sentinel in seq space
_NEVER = np.float64(np.inf)     # "never fails"


# ---------------------------------------------------------------- lowering
@dataclasses.dataclass
class DeviceLowering:
    """One run lowered to batched-parameter form (host numpy arrays)."""
    chunk_costs: np.ndarray      # [C] nominal compute seconds per chunk
    chunk_sizes: np.ndarray      # [C] tasks per chunk (last may be partial)
    n_chunks: int
    chunk: int                   # the technique's fixed chunk size
    P: int
    h: float
    lat: float
    speed: float
    rdlb: bool
    fail_time: np.ndarray        # [P] fail-stop instants (inf = never)
    N: int
    horizon: float
    technique: str = ""
    label: str = ""


def lower_run(spec, task_times, *,
              technique=None) -> tuple[Optional[DeviceLowering], str]:
    """Try to lower ``(spec, task_times)`` into device-batched form.

    Returns ``(lowering, "")`` or ``(None, reason)``.  The checks mirror
    ``fastpath.fast_forward`` eligibility, extended to whole runs:
    fail-stop DRAWS are allowed (they batch as the perturbation axis),
    heterogeneity/adaptivity/barriers/finite dup caps are not.
    """
    if spec.execution.mode != "virtual":
        return None, f"mode={spec.execution.mode!r} (need virtual)"
    if spec.adaptive.enabled:
        return None, "adaptive policy enabled"
    h = float(spec.execution.h)
    if h <= 0.0:
        return None, "h <= 0"
    if spec.robustness.max_duplicates is not None:
        return None, "finite max_duplicates (poll/cap paths are scalar-only)"
    times = np.asarray(task_times, dtype=np.float64)
    N = len(times)
    if N < 1:
        return None, "empty workload"
    ws = spec.cluster.worker_specs()
    P = len(ws)
    if P < 1:
        return None, "no workers"
    speed, lat = float(ws[0].speed), float(ws[0].msg_latency)
    if speed <= 0.0:
        return None, "non-positive speed"
    fail = np.full(P, _NEVER)
    for i, w in enumerate(ws):
        if not w.alive:
            return None, f"worker {i} starts dead"
        if w.fail_after_tasks is not None:
            return None, f"worker {i} has count-based fail-stop"
        if w.speed != speed or w.msg_latency != lat:
            return None, "heterogeneous workers"
        stops = [t for t in (w.fail_time, w.hang_time) if t is not None]
        if stops:
            fail[i] = min(stops)
    tech = technique
    if tech is None:
        sched = spec.scheduling
        tech = dls.make_technique(sched.technique, N, P, seed=sched.seed,
                                  **sched.param_dict())
    if getattr(tech, "barrier_per_batch", False):
        return None, f"{tech.name}: batch-weight barrier technique"
    c = tech.fixed_chunk()
    if c is None or c < 1:
        return None, f"{tech.name}: not a fixed-chunk technique"
    C = -(-N // c)
    # (near-)uniform task costs over all FULL chunks: the round-robin
    # serve-order proof needs the per-chunk spread to vanish against the
    # master's h spacing (same threshold as fastpath).  The final partial
    # chunk is exempt — its ordering is computed exactly in the tail.
    nfull = (C - 1) * c if C > 1 else N
    if nfull > 0:
        d = times[:nfull]
        dmin, dmax = float(d.min()), float(d.max())
        if not (np.isfinite(dmin) and np.isfinite(dmax)) or dmin < 0.0:
            return None, "non-finite/negative task costs"
        if (dmax - dmin) * c >= h * 1e-6:
            return None, "task-cost spread too large for round-robin proof"
    ctime = np.concatenate([[0.0], np.cumsum(times)])
    starts = np.arange(C, dtype=np.int64) * c
    stops = np.minimum(starts + c, N)
    return DeviceLowering(
        chunk_costs=(ctime[stops] - ctime[starts]).astype(np.float64),
        chunk_sizes=(stops - starts).astype(np.int32),
        n_chunks=int(C), chunk=int(c), P=P, h=h, lat=lat, speed=speed,
        rdlb=bool(spec.robustness.rdlb_enabled), fail_time=fail, N=N,
        horizon=float(spec.execution.horizon),
        technique=spec.scheduling.technique,
        label=spec.name or spec.scheduling.technique), ""


# ------------------------------------------------------------ batch result
@dataclasses.dataclass
class DeviceBatchResult:
    """Per-element outputs of one batched device call (host numpy)."""
    t_par: np.ndarray            # [B] (inf = hang)
    hung: np.ndarray             # [B] bool
    valid: np.ndarray            # [B] bool: False -> re-run on the scalar
                                 # engine (budget exhausted / unlowerable)
    n_finished: np.ndarray       # [B]
    n_assignments: np.ndarray    # [B]
    n_duplicates: np.ndarray     # [B]
    wasted_tasks: np.ndarray     # [B]
    pe_busy: np.ndarray          # [B, P]
    pe_idle: np.ndarray          # [B, P]
    tasks_done: np.ndarray       # [B, P]
    last_done: np.ndarray        # [B, P]


# ------------------------------------------------------------- round phase
def _round_phase(st, const, *, P, R_max, nofail=False):
    """lax.scan over assignment rounds.  ``st`` carries per-worker arrival
    times / in-flight chunks / liveness; each step is one full service
    round: cummax masters, cumsum chunk hand-out, death filtering.

    ``nofail`` (static) specializes for elements with no fail-stop draws
    (the clean tails' precondition): the piggyback gate, loss check and
    death bookkeeping vanish from the compiled scan step."""
    cost_at, size_at, nc, fail, h, lat, speed = const
    widx = jnp.arange(P, dtype=jnp.int32)

    def step(st, _):
        (arrive, held, first, dead, nxt, mfree, nleft,
         tasks, busy, last_done, n_assign) = st
        part = jnp.isfinite(arrive)
        active = (nxt + P <= nc) & part.any()
        rank = jnp.cumsum(part.astype(jnp.int32)) - 1
        a = jnp.where(part, arrive - rank * h, -jnp.inf)
        M = jnp.maximum(lax.cummax(a), mfree) + (rank + 1) * h
        # commits: every served report finishes its held chunk (no
        # duplicates can exist inside the window, so every commit wins)
        commit = part & (held >= 0)
        heldc = jnp.clip(held, 0, None)
        nleft2 = nleft - jnp.where(commit, size_at(heldc), 0).sum()
        # piggyback gate (round 0 = initial requests: unconditional)
        if nofail:
            take = part
        else:
            take = part & (first | (M < fail[widx]))
        idx = nxt + jnp.cumsum(take.astype(jnp.int32)) - 1
        idxc = jnp.clip(idx, 0, nc - 1)
        cost = cost_at(idxc) / speed
        done = M + lat + cost
        if nofail:
            ok = take
            dead2 = dead
        else:
            lost = take & (done >= fail[widx])
            ok = take & ~lost
            dead2 = dead | lost
        arrive2 = jnp.where(ok, done + lat, jnp.inf)
        arrive2 = jnp.where(part, arrive2, arrive)
        held2 = jnp.where(take, idx, jnp.where(part, -1, held))
        tasks2 = tasks + jnp.where(ok, size_at(idxc), 0)
        busy2 = busy + jnp.where(ok, cost, 0.0)
        last2 = jnp.where(ok, done, last_done)
        mfree2 = jnp.max(jnp.where(part, M, -jnp.inf))
        mfree2 = jnp.where(part.any(), mfree2, mfree)
        ntake = jnp.sum(take, dtype=jnp.int32)
        new = (arrive2, held2, jnp.zeros_like(first), dead2,
               nxt + ntake, mfree2, nleft2, tasks2, busy2, last2,
               n_assign + ntake)
        st = tuple(jnp.where(active, n, o) for n, o in zip(new, st))
        return st, None

    st, _ = lax.scan(step, st, None, length=R_max)
    return st


# ---------------------------------------------------- clean (no-fail) tail
def _round_b(st_b, const, *, P, r, M_B, orderB):
    """Round B: the first r-1 served remainder reports each trigger one
    more rDLB duplicate (queue not yet done) — an O(r) micro-loop walks
    the re-issue ring pointer exactly.  Shared by both clean tails."""
    cost_at, size_at, nc, fail, h, lat, speed, rdlb = const

    def stepB(j, carry):
        candseq, ptr, dupmin, tasks, busy, last_done, n_assign, n_dups \
            = carry
        o = orderB[j]
        candseq = candseq.at[o].set(_BIG)     # its chunk commits first
        ge = jnp.where(candseq >= ptr, candseq, _BIG)
        s1 = jnp.min(ge)
        s2 = jnp.where(s1 == _BIG, jnp.min(candseq), s1)
        can = rdlb & (s2 != _BIG)
        s2c = jnp.clip(s2, 0, nc - 1)
        dc = cost_at(s2c) / speed
        dn = M_B[j] + lat + dc
        tasks = tasks.at[o].add(jnp.where(can, size_at(s2c), 0))
        busy = busy.at[o].add(jnp.where(can, dc, 0.0))
        last_done = last_done.at[o].set(jnp.where(can, dn, last_done[o]))
        dupmin = jnp.where(can, jnp.minimum(dupmin, dn + lat), dupmin)
        ptr = jnp.where(can, s2 + 1, ptr)
        n_assign = n_assign + can.astype(jnp.int32)
        n_dups = n_dups + can.astype(jnp.int32)
        return (candseq, ptr, dupmin, tasks, busy, last_done,
                n_assign, n_dups)

    return lax.fori_loop(0, jnp.clip(r - 1, 0, P), stepB, st_b)


def _clean_tail(st, const, *, P):
    """General tail for failure-free elements: round A serves the P
    in-flight reports in exact arrival order (stable argsort = the heap's
    tie-break on push order), handing the first r serve-ranks the
    remainder originals and walking the re-issue ring for the rDLB
    duplicates; then round B serves the r remainder reports the same way.
    An O(P) micro-loop reproduces the ring pointer exactly — correct even
    when the final partial chunk is already in flight and reports out of
    index order, at O(P^2) cost per element.

    Validity (-> scalar fallback, never a wrong answer) additionally
    requires phase separation: every remainder report must arrive after
    all round-A reports, and every duplicate report after all original
    reports — guaranteed for uniform full chunks, but a very cheap
    partial chunk against a large P*h master span can violate it."""
    cost_at, size_at, nc, fail, h, lat, speed, rdlb = const
    (arrive, held, first, dead, nxt, mfree, nleft,
     tasks, busy, last_done, n_assign) = st
    valid = (~first.any()) & (nxt + P > nc)   # >=1 round ran, none left
    r = nc - nxt                              # remainder chunks, 0 <= r < P
    w = jnp.arange(P, dtype=jnp.int32)

    # ---- round A: serve the P in-flight reports in arrival order
    orderA = jnp.argsort(arrive, stable=True)
    Ms = jnp.maximum(lax.cummax(arrive[orderA] - w * h),
                     mfree) + (w + 1) * h     # masters, in serve order

    def stepA(k, carry):
        (candseq, ptr, arrB, dupmin, tasks, busy, last_done,
         n_assign, n_dups) = carry
        o = orderA[k]
        candseq = candseq.at[o].set(_BIG)     # o's held chunk commits
        is_orig = k < r
        done_after = (r == 0) & (k == P - 1)  # queue done at last commit
        ge = jnp.where(candseq >= ptr, candseq, _BIG)
        s1 = jnp.min(ge)
        s2 = jnp.where(s1 == _BIG, jnp.min(candseq), s1)
        can_dup = rdlb & (~is_orig) & (~done_after) & (s2 != _BIG)
        tgt = jnp.where(is_orig, nxt + k, s2)
        tgtc = jnp.clip(tgt, 0, nc - 1)
        cost = cost_at(tgtc) / speed
        dn = Ms[k] + lat + cost
        assigned = is_orig | can_dup
        tasks = tasks.at[o].add(jnp.where(assigned, size_at(tgtc), 0))
        busy = busy.at[o].add(jnp.where(assigned, cost, 0.0))
        last_done = last_done.at[o].set(
            jnp.where(assigned, dn, last_done[o]))
        arrB = arrB.at[o].set(jnp.where(is_orig, dn + lat, jnp.inf))
        dupmin = jnp.where(can_dup, jnp.minimum(dupmin, dn + lat), dupmin)
        candseq = candseq.at[o].set(jnp.where(is_orig, tgt, _BIG))
        ptr = jnp.where(can_dup, s2 + 1, ptr)
        n_assign = n_assign + assigned.astype(jnp.int32)
        n_dups = n_dups + can_dup.astype(jnp.int32)
        return (candseq, ptr, arrB, dupmin, tasks, busy, last_done,
                n_assign, n_dups)

    carry = (jnp.where(held >= 0, held, _BIG).astype(jnp.int32),
             jnp.zeros((), jnp.int32), jnp.full(P, jnp.inf),
             jnp.asarray(jnp.inf, jnp.float64), tasks, busy, last_done,
             n_assign, jnp.zeros((), jnp.int32))
    (candseq, ptr, arrB, dupmin, tasks, busy, last_done,
     n_assign, n_dups) = lax.fori_loop(0, P, stepA, carry)

    # ---- round B: the r remainder reports, in exact arrival order
    orderB = jnp.argsort(arrB, stable=True)
    sortB = jnp.where(w < r, arrB[orderB] - w * h, -jnp.inf)
    M_B = jnp.maximum(lax.cummax(sortB), Ms[P - 1]) + (w + 1) * h
    # t_par: r == 0 completes at round A's last commit, else at the last
    # remainder report's master transaction
    t_par = jnp.where(r >= 1, M_B[jnp.clip(r - 1, 0, P - 1)], Ms[P - 1])

    carry = (candseq, ptr, dupmin, tasks, busy, last_done,
             n_assign, n_dups)
    (candseq, ptr, dupmin, tasks, busy, last_done, n_assign, n_dups) = \
        _round_b(carry, const, P=P, r=r, M_B=M_B, orderB=orderB)

    # phase separation: remainder reports strictly follow round A, dup
    # reports follow every original report (ties resolve to the original
    # via heap push order, hence >=)
    maxA = jnp.max(arrive)
    minB = jnp.min(arrB)
    maxorig = jnp.maximum(maxA, jnp.max(jnp.where(jnp.isfinite(arrB),
                                                  arrB, -jnp.inf)))
    valid = valid & ((r == 0) | (minB >= maxA)) & (dupmin >= maxorig)

    zero = jnp.zeros((), jnp.int32)
    return (t_par, jnp.zeros((), bool), valid, nleft * 0,
            n_assign, n_dups, zero, tasks, busy, last_done, ~dead)


def _clean_tail_sorted(st, const, *, P):
    """Fully-vectorized tail for failure-free elements whose round-A serve
    order provably equals worker-index order — the common case where the
    in-flight chunks are all FULL (host-gated: nc % P != 0, or the last
    chunk is full; device-checked: ``arrive`` is non-decreasing).  No
    O(P) micro-loop: round A is one cummax, the re-issue ring closed-form
    (at serve rank w >= r the cyclic-min candidate is worker w+1's held
    chunk; rank P-1 re-issues the first remainder original), so the
    per-element cost is O(P log P) — this is what makes the 10^4-element
    portfolio/Monte-Carlo batches fast.  Round B (the r remainder
    reports, which MAY be out of order — the partial chunk is cheap)
    reuses the exact O(r) ring walk.

    Same phase-separation validity contract as :func:`_clean_tail`."""
    cost_at, size_at, nc, fail, h, lat, speed, rdlb = const
    (arrive, held, first, dead, nxt, mfree, nleft,
     tasks, busy, last_done, n_assign) = st
    valid = (~first.any()) & (nxt + P > nc)   # >=1 round ran, none left
    valid = valid & jnp.all(jnp.diff(arrive) >= 0.0)   # index-sorted
    r = nc - nxt                              # remainder chunks, 0 <= r < P
    w = jnp.arange(P, dtype=jnp.int32)

    # ---- round A, serve order == index order
    M_A = jnp.maximum(lax.cummax(arrive - w * h), mfree) + (w + 1) * h
    is_orig = w < r
    done_after = (r == 0) & (w == P - 1)      # queue done at last commit
    # ring closed-form: ptr starts at 0; the cyclic-min unfinished holder
    # at rank w is worker w+1 (chunks nxt-P+w+1 ascend), until rank P-1
    # where only the round's own originals (nxt..nxt+r-1) remain
    dup_t = jnp.where(w < P - 1, held[(w + 1) % P], nxt)
    can_dup = rdlb & ~is_orig & ~done_after
    tgt = jnp.where(is_orig, nxt + w, dup_t)
    tgtc = jnp.clip(tgt, 0, nc - 1)
    cost = cost_at(tgtc) / speed
    dn = M_A + lat + cost
    assigned = is_orig | can_dup
    tasks = tasks + jnp.where(assigned, size_at(tgtc), 0)
    busy = busy + jnp.where(assigned, cost, 0.0)
    last_done = jnp.where(assigned, dn, last_done)
    arrB = jnp.where(is_orig, dn + lat, jnp.inf)
    dupmin = jnp.min(jnp.where(can_dup, dn + lat, jnp.inf))
    n_assign = n_assign + jnp.sum(assigned, dtype=jnp.int32)
    n_dups = jnp.sum(can_dup, dtype=jnp.int32)

    # ---- round B: the r remainder reports, in exact arrival order
    orderB = jnp.argsort(arrB, stable=True)
    sortB = jnp.where(w < r, arrB[orderB] - w * h, -jnp.inf)
    M_B = jnp.maximum(lax.cummax(sortB), M_A[P - 1]) + (w + 1) * h
    t_par = jnp.where(r >= 1, M_B[jnp.clip(r - 1, 0, P - 1)], M_A[P - 1])

    # ring state after round A: originals nxt+w live at workers w < r;
    # rank P-1's re-issue advanced the pointer past nxt
    candseq = jnp.where(is_orig, nxt + w, _BIG).astype(jnp.int32)
    ptr = jnp.where(rdlb & (r >= 1), nxt + 1, 0).astype(jnp.int32)
    carry = (candseq, ptr, dupmin, tasks, busy, last_done,
             n_assign, n_dups)
    (candseq, ptr, dupmin, tasks, busy, last_done, n_assign, n_dups) = \
        _round_b(carry, const, P=P, r=r, M_B=M_B, orderB=orderB)

    # phase separation (see _clean_tail)
    maxA = jnp.max(arrive)
    minB = jnp.min(arrB)
    maxorig = jnp.maximum(maxA, jnp.max(jnp.where(jnp.isfinite(arrB),
                                                  arrB, -jnp.inf)))
    valid = valid & ((r == 0) | (minB >= maxA)) & (dupmin >= maxorig)

    zero = jnp.zeros((), jnp.int32)
    return (t_par, jnp.zeros((), bool), valid, nleft * 0,
            n_assign, n_dups, zero, tasks, busy, last_done, ~dead)


# -------------------------------------------------- transaction-phase tail
def _txn_tail(st, const, *, P, T_max):
    """Exact event-at-a-time tail for elements with failure draws: each
    scan step serves the earliest pending arrival (the event heap's next
    master transaction) — commit / first-completion-wins / ring re-issue
    / duplicate-slot leak / retirement / Fig.-1b hang semantics exactly
    as ``Engine.run``."""
    cost_at, size_at, nc, fail, h, lat, speed, rdlb = const
    widx = jnp.arange(P, dtype=jnp.int32)
    (arrive, held, first, dead, nxt, mfree, nleft,
     tasks, busy, last_done, n_assign) = st
    isdup = jnp.zeros(P, bool)
    hfin = jnp.zeros(P, bool)                 # holding an already-won chunk
    dupc = jnp.zeros(P, jnp.int32)            # live dups, at the ORIGINAL
                                              # holder's slot (leaks when a
                                              # dup holder dies — as rdlb's
                                              # _c_dups does)
    ptr = jnp.zeros((), jnp.int32)            # re-issue ring pointer (seq)
    t_par = jnp.asarray(jnp.inf, jnp.float64)
    fin = jnp.zeros((), bool)
    hung = jnp.zeros((), bool)
    n_dups = jnp.zeros((), jnp.int32)
    wasted = jnp.zeros((), jnp.int32)

    def step(st, _):
        (arrive, held, first, dead, isdup, hfin, dupc, ptr, nxt, mfree,
         nleft, t_par, fin, hung, tasks, busy, last_done, n_assign,
         n_dups, wasted) = st
        pend = jnp.isfinite(arrive)
        go = ~(fin | hung) & pend.any()
        newhang = ~(fin | hung) & ~pend.any() & (nleft > 0)
        i = jnp.argmin(jnp.where(pend, arrive, jnp.inf))
        tm = jnp.maximum(arrive[i], mfree) + h
        isreq = first[i]

        # ---- report service (no-op fields when isreq)
        rep = go & ~isreq & (held[i] >= 0)
        s = jnp.clip(held[i], 0, nc - 1)
        ssz = size_at(s)
        win = rep & ~hfin[i]
        lose = rep & hfin[i]
        nleft2 = nleft - jnp.where(win, ssz, 0)
        wasted2 = wasted + jnp.where(lose, ssz, 0)
        # first-completion-wins: other holders of s now hold dead weight
        hfin2 = hfin | (win & (held == held[i]))
        # a live dup's report frees its slot at the ORIGINAL holder
        oslot = (held == held[i]) & ~isdup & (held >= 0) & (widx != i)
        dec = rep & isdup[i]
        dupc2 = dupc - jnp.where(dec & oslot, 1, 0)
        # clear the reporter's slot
        served = go & ~isreq
        held2 = jnp.where(served & (widx == i), -1, held)
        isdup2 = jnp.where(served & (widx == i), False, isdup)
        hfin2 = jnp.where(served & (widx == i), False, hfin2)
        newly_done = win & (nleft2 == 0)
        fin2 = fin | (go & newly_done)
        t_par2 = jnp.where(go & newly_done, tm, t_par)

        # ---- assignment (REQ_ARRIVE always assigns; a report piggybacks
        # only while the worker is alive at the master's end instant)
        want = isreq | (~newly_done & (tm < fail[i]))
        have_orig = nxt < nc
        cand = (held2 >= 0) & ~isdup2 & ~hfin2
        seqs = jnp.where(cand, held2, _BIG)
        ge = jnp.where(seqs >= ptr, seqs, _BIG)
        s1 = jnp.min(ge)
        s2 = jnp.where(s1 == _BIG, jnp.min(seqs), s1)
        can_dup = rdlb & (s2 != _BIG)
        assigned = go & want & (have_orig | can_dup)
        as_dup = assigned & ~have_orig
        tgt = jnp.where(have_orig, nxt, s2)
        tgtc = jnp.clip(tgt, 0, nc - 1)
        ptr2 = jnp.where(as_dup, s2 + 1, ptr)
        dupc2 = dupc2 + jnp.where(as_dup & (held2 == s2) & ~isdup2, 1, 0)
        cost = cost_at(tgtc) / speed
        done = tm + lat + cost
        lostx = assigned & (done >= fail[i])
        okx = assigned & ~lostx
        mine = widx == i
        held3 = jnp.where(assigned & mine, tgt, held2)
        isdup3 = jnp.where(assigned & mine, as_dup, isdup2)
        dead2 = dead | (lostx & mine)
        arrive2 = jnp.where(go & mine,
                            jnp.where(okx, done + lat, jnp.inf), arrive)
        first2 = jnp.where(go & mine, False, first)
        tasks2 = tasks + jnp.where(okx & mine, size_at(tgtc), 0)
        busy2 = busy + jnp.where(okx & mine, cost, 0.0)
        last2 = jnp.where(okx & mine, done, last_done)
        st = (arrive2, held3, first2, dead2, isdup3, hfin2, dupc2, ptr2,
              jnp.where(assigned & have_orig, nxt + 1, nxt),
              jnp.where(go, tm, mfree),
              jnp.where(go, nleft2, nleft), t_par2, fin2,
              hung | newhang, tasks2, busy2, last2,
              n_assign + assigned.astype(jnp.int32),
              n_dups + as_dup.astype(jnp.int32),
              jnp.where(go, wasted2, wasted))
        return st, None

    st = (arrive, held, first, dead, isdup, hfin, dupc, ptr, nxt, mfree,
          nleft, t_par, fin, hung, tasks, busy, last_done, n_assign,
          n_dups, wasted)
    st, _ = lax.scan(step, st, None, length=T_max)
    (arrive, held, first, dead, isdup, hfin, dupc, ptr, nxt, mfree,
     nleft, t_par, fin, hung, tasks, busy, last_done, n_assign,
     n_dups, wasted) = st
    t_par = jnp.where(hung, jnp.inf, t_par)
    return (t_par, hung, fin | hung, nleft, n_assign, n_dups, wasted,
            tasks, busy, last_done, ~dead)


# ------------------------------------------------------------ one element
_TAILS = ("sorted", "general", "txn")


def _simulate_one(tech_ix, rdlb, fail, h, lat, speed, tables, *,
                  P, R_max, T_max, tail):
    t_costs, t_sizes, t_nc, t_N = tables
    nc = t_nc[tech_ix]
    N = t_N[tech_ix]

    # 2-D gathers keyed on (element technique, chunk index): XLA never
    # materializes a per-element [C] cost row, which matters at
    # B x C ~ 10^3 x 10^5
    def cost_at(i):
        return t_costs[tech_ix, i]

    def size_at(i):
        return t_sizes[tech_ix, i]

    st = (jnp.full(P, lat, jnp.float64),           # arrive (REQ_ARRIVE)
          jnp.full(P, -1, jnp.int32),              # held chunk
          jnp.ones(P, bool),                       # first (initial request)
          jnp.zeros(P, bool),                      # dead
          jnp.zeros((), jnp.int32),                # next_chunk
          jnp.zeros((), jnp.float64),              # master_free
          N.astype(jnp.int64),                     # tasks left
          jnp.zeros(P, jnp.int32),                 # tasks_done
          jnp.zeros(P, jnp.float64),               # busy
          jnp.zeros(P, jnp.float64),               # last_done
          jnp.zeros((), jnp.int32))                # n_assignments
    const_r = (cost_at, size_at, nc, fail, h, lat, speed)
    st = _round_phase(st, const_r, P=P, R_max=R_max,
                      nofail=(tail != "txn"))
    const_t = const_r + (rdlb,)
    if tail == "sorted":
        return _clean_tail_sorted(st, const_t, P=P)
    if tail == "general":
        return _clean_tail(st, const_t, P=P)
    return _txn_tail(st, const_t, P=P, T_max=T_max)


_COMPILE_CACHE: dict = {}


def _compiled(P, C, R_max, T_max, tail):
    """jit-compiled vmapped batch simulator, cached on the static dims
    (C only keys the cache — the table shapes retrace on change)."""
    assert tail in _TAILS
    key = (P, C, R_max, T_max, tail)
    fn = _COMPILE_CACHE.get(key)
    if fn is not None:
        return fn

    def batch(tech_ix, rdlb, fail, h, lat, speed, t_costs, t_sizes,
              t_nc, t_N):
        tables = (t_costs, t_sizes, t_nc, t_N)

        def one(ix, rd, fl, hh, ll, sp):
            return _simulate_one(ix, rd, fl, hh, ll, sp, tables,
                                 P=P, R_max=R_max, T_max=T_max,
                                 tail=tail)

        return jax.vmap(one)(tech_ix, rdlb, fail, h, lat, speed)

    fn = jax.jit(batch)
    _COMPILE_CACHE[key] = fn
    return fn


def _bucket(n: int) -> int:
    """Round scan budgets up to sub-octave buckets: bounded recompilation,
    small masked scan-step overhead (a plain power-of-2 budget wastes up
    to 2x).  Small budgets (cheap to recompile, hot in adaptive sweeps)
    use quarter-octave steps, large ones (benchmark/Monte-Carlo scale,
    where wasted steps dominate compile time) eighth-octave."""
    if n <= 16:
        return 16
    b = 16
    while b < n:
        b *= 2
    q = b // 8 if b < 256 else b // 16
    return -(-n // q) * q


# --------------------------------------------------------------- host API
def batch_program(lowerings: Sequence[DeviceLowering], tech_of: np.ndarray,
                  fail: np.ndarray, tail: str) -> tuple:
    """``(fn, args)``: the jitted ``tail`` batch simulator for elements
    ``tech_of`` (indices into ``lowerings``) with per-worker fail-stop
    instants ``fail`` [B, P], and its host arguments — what
    :func:`simulate_many` runs, exposed so a compile check can lower it
    without running it."""
    P = lowerings[0].P
    U = len(lowerings)
    C = max(lo.n_chunks for lo in lowerings)
    t_costs = np.zeros((U, C))
    t_sizes = np.zeros((U, C), dtype=np.int32)
    t_nc = np.zeros(U, dtype=np.int32)
    t_N = np.zeros(U, dtype=np.int64)
    for u, lo in enumerate(lowerings):
        t_costs[u, :lo.n_chunks] = lo.chunk_costs
        t_sizes[u, :lo.n_chunks] = lo.chunk_sizes
        t_nc[u] = lo.n_chunks
        t_N[u] = lo.N
    k_max = int(np.isfinite(fail).sum(axis=1).max(initial=0))
    surv = max(1, P - k_max)
    R_max = _bucket(int(-(-int(t_nc[tech_of].max()) // surv)) + 2)
    T_max = _bucket(4 * P + 16 * k_max + 64) if tail == "txn" else 0
    of = lambda field: np.array([getattr(lowerings[u], field)
                                 for u in tech_of])
    return (_compiled(P, C, R_max, T_max, tail),
            (tech_of, of("rdlb"), fail, of("h"), of("lat"), of("speed"),
             t_costs, t_sizes, t_nc, t_N))


def simulate_many(lowerings: Sequence[DeviceLowering],
                  tech_of: Optional[np.ndarray] = None,
                  fail_times: Optional[np.ndarray] = None
                  ) -> DeviceBatchResult:
    """ONE batched device call (well: at most three jit calls — failure-
    free elements take a closed-form tail, vectorized when the serve
    order is provably index order and an exact O(P) ring walk otherwise;
    failure draws take the exact transaction scan) over B = len(tech_of)
    elements.

    ``tech_of[b]`` indexes into ``lowerings`` (the candidate axis);
    ``fail_times[b]`` is a per-worker fail-stop draw (inf = never),
    combined (min) with each lowering's own spec-declared instants.
    Defaults: one element per lowering, no extra draws.
    """
    if not lowerings:
        raise ValueError("need at least one lowering")
    P = lowerings[0].P
    if any(lo.P != P for lo in lowerings):
        raise ValueError("all lowerings in a batch must share P")
    if tech_of is None:
        tech_of = np.arange(len(lowerings), dtype=np.int32)
    tech_of = np.asarray(tech_of, dtype=np.int32)
    B = len(tech_of)
    spec_fail = np.stack([lo.fail_time for lo in lowerings])[tech_of]
    if fail_times is None:
        fail = spec_fail
    else:
        fail = np.minimum(np.asarray(fail_times, dtype=np.float64),
                          spec_fail)
    nc_of = np.array([lo.n_chunks for lo in lowerings])[tech_of]
    t_N = np.array([lo.N for lo in lowerings], dtype=np.int64)

    clean_mask = (np.isfinite(fail).sum(axis=1) == 0) & (nc_of >= P)
    # serve order == index order unless P | nc AND the last chunk is
    # partial (then the cheap partial chunk is in flight during the tail's
    # round A and reports early) — those take the O(P) ring-walk tail
    lo_sorted = np.array([(lo.n_chunks % P != 0)
                          or (lo.chunk_sizes[-1] == lo.chunk)
                          for lo in lowerings])
    sorted_mask = clean_mask & lo_sorted[tech_of]

    out = {
        "t_par": np.full(B, np.inf), "hung": np.zeros(B, bool),
        "valid": np.zeros(B, bool), "n_finished": np.zeros(B, np.int64),
        "n_assignments": np.zeros(B, np.int64),
        "n_duplicates": np.zeros(B, np.int64),
        "wasted_tasks": np.zeros(B, np.int64),
        "pe_busy": np.zeros((B, P)), "pe_idle": np.zeros((B, P)),
        "tasks_done": np.zeros((B, P), np.int64),
        "last_done": np.zeros((B, P)),
    }

    def run_sub(idx: np.ndarray, tail: str) -> None:
        if len(idx) == 0:
            return
        fn, args = batch_program(lowerings, tech_of[idx], fail[idx], tail)
        res = fn(*(jnp.asarray(a) for a in args))
        (t_par, hung, valid, nleft, n_assign, n_dups, wasted,
         tasks, busy, last_done, _) = (np.asarray(x) for x in res)
        out["t_par"][idx] = t_par
        out["hung"][idx] = hung
        out["valid"][idx] = valid
        out["n_finished"][idx] = t_N[tech_of[idx]] - nleft
        out["n_assignments"][idx] = n_assign
        out["n_duplicates"][idx] = n_dups
        out["wasted_tasks"][idx] = wasted
        out["pe_busy"][idx] = busy
        out["tasks_done"][idx] = tasks
        out["last_done"][idx] = last_done

    with jax.enable_x64(True):
        run_sub(np.flatnonzero(sorted_mask), "sorted")
        run_sub(np.flatnonzero(clean_mask & ~sorted_mask), "general")
        run_sub(np.flatnonzero(~clean_mask), "txn")

    # horizon: the engine declares a hang when the finishing event pops
    # past it — lowered runs never poll, so t_par is the only check
    horizon = np.array([lowerings[u].horizon for u in tech_of])
    over = out["valid"] & ~out["hung"] & (out["t_par"] > horizon)
    out["hung"] |= over
    out["t_par"][over] = np.inf
    # idle: same derivation as EngineStats (zeros on hang)
    ok = out["valid"] & ~out["hung"]
    end = np.minimum(out["t_par"][:, None],
                     np.where(np.isfinite(fail), fail, np.inf))
    end = np.minimum(end, np.where(np.isinf(out["t_par"][:, None]),
                                   0.0, out["t_par"][:, None]))
    idle = np.maximum(0.0, end - out["pe_busy"])
    out["pe_idle"] = np.where(ok[:, None], idle, 0.0)
    return DeviceBatchResult(**out)


def simulate_spec(spec, task_times,
                  fail_times: Optional[np.ndarray] = None
                  ) -> Optional[DeviceBatchResult]:
    """Convenience wrapper: lower one spec and batch it over ``fail_times``
    draws ([D, P], inf = never).  Returns None when the spec is outside
    the lowered regime (callers fall back to the scalar engine)."""
    lo, _ = lower_run(spec, task_times)
    if lo is None:
        return None
    D = 1 if fail_times is None else len(fail_times)
    return simulate_many([lo], tech_of=np.zeros(D, np.int32),
                         fail_times=fail_times)
