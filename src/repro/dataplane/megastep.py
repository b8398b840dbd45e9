"""Device-resident megastep: N ticks inside ONE compiled scan (DESIGN.md §13).

The sequential runtime round-trips through Python for every tick —
dispatch, per-queue kernel launches, retire — so throughput *falls* as
queues scale.  This module keeps the hot-path state resident on device
and replays a whole *window* of ticks in one compiled program:

* ``DeviceState``: the flattened multi-queue ring pytree
  (`repro.dataplane.ring.device_rings`) is the scan carry and persists
  across flushes (donated into every call, so the ring buffer is
  updated in place, never copied).
* The ``lax.scan`` replays the window's ring traffic: each staged tick
  pushes its arrival bursts, pops up to ``batch`` rows FIFO from every
  ring, and *compacts* them queue-major into one ``(width, ...)`` slab.
  The scan moves rows, not verdicts — it emits only the three key words
  each popped row needs downstream (slot id, control word, first
  payload word), so a tick costs a handful of gathers.
* The forwarding math for the WHOLE window then runs as one batched
  launch after the scan: all queues, all ticks, one program.  It
  exploits a payload-structure invariant the host mirror verifies per
  flush: any two rows whose payload *suffix* (words 1..255) is
  identical share the suffix part of the XNOR-popcount, so the kernel
  computes each distinct ``(suffix, effective-slot)`` pair once and
  per-row work collapses to a single-word popcount plus the tiny dense
  head.  The decomposition is exact integer arithmetic — verdicts are
  bit-identical to the per-row path for ANY traffic; repeated flows
  just make it fast.
* Control epochs are applied eagerly to the host mirrors (so atomic
  apply, rollback, and the epoch log keep their exact semantics) and
  *also* serialized as ``DeviceDelta`` entries into a bounded epoch
  queue (`repro.control.plane.serialize_device_delta`).  At flush the
  delta params are stacked behind the window's base bank as an
  *extended bank* on device; every popped row carries the extended
  index of the bank version live at its tick, so mid-window SwapSlot
  transitions resolve per row with no in-scan weight mutation.
* Telemetry counters accumulate on device (scan carry + batched
  scatters); verdict/slot/action slabs come back shaped ``(T, width)``.
  Both drain to the Python side ONCE per flush: bulk counter fold
  (``Telemetry.record_window``), then one pass over the staged window
  for the obs/deploy taps and the trace recorder — per-megastep, not
  per-tick.

The host ``PacketRing`` mirror stays fully authoritative for counters,
timestamps, routing, and policy views: ``dispatch``/``tick`` stage the
work *and* run the deterministic host-side ring simulation, so every
host-visible return value is exact without a device sync.  The device
rings must reproduce the mirror's row flow bit-for-bit; the flush
asserts the two agree on per-queue pop counts.

Bit-exactness contract (the hypothesis property in
``tests/test_megastep.py``): verdicts, slots, actions, telemetry count
totals, and epoch apply ticks are identical to N sequential ``tick()``
calls.  Wall-clock readings (latency histograms, epoch
``apply_latency_us``) are taken at flush granularity instead and are
outside the contract.
"""

from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.control.plane import (DELTA_RETA, DELTA_SWAP,
                                 serialize_device_delta)
from repro.core import packet as pkt
from repro.dataplane import ring as ring_lib
from repro.dataplane.workloads.phases import SEQ_WORD
from repro.kernels import fused_forward as _fusedk
from repro.kernels import ref as _refk

#: Bounded on-device epoch queue depth per window.  The runtime flushes
#: the window before applying an epoch batch that would not fit, so the
#: queue can never overflow mid-transaction.
EPOCH_CAPACITY = 8

#: Fixed device RETA mirror length (tables are padded / truncated).
DEVICE_RETA_SIZE = 128

#: Shape quantization for the compiled-variant cache: burst capacity,
#: compaction width, scan length and suffix-table size round up to
#: these, so phase-constant traces reuse a handful of compiled programs
#: instead of one per flush.
_BURST_GRAIN = 64
_WIDTH_GRAIN = 32
_TICK_GRAIN = 8
_SUFFIX_GRAIN = 64

#: Word columns the non-audit scan emits per popped row: slot id,
#: control word, first payload word — everything the batched forward
#: needs that is not covered by the deduplicated payload suffix.
_KEY_COLS = (pkt.SLOT_WORD, pkt.CONTROL_WORD_LO, pkt.META_WORDS)

#: Fixed fold for the host-side suffix hash: an f64 dot over a fixed
#: sample of suffix columns (BLAS, ~16x cheaper than hashing all 255).
#: The hash only *accelerates* grouping — group membership is verified
#: by exact full-width comparison and falls back to a full
#: lexicographic unique, so a collision can never change results.
_HASH_COLS = np.linspace(0, pkt.PAYLOAD_WORDS - 2, 16).astype(np.intp)
_HASH_VEC = np.cos((_HASH_COLS + 1) * 0.7310585786300049) * 65537.0
_HASH_ES = 2654435761.000001


def _round_up(n: int, g: int) -> int:
    return ((int(n) + g - 1) // g) * g


@dataclasses.dataclass
class _Staged:
    """One staged (deferred) tick: the host mirror already popped its
    rows; the device replays the same push/pop/compute at flush."""
    tick: int                # runtime tick id (``_tick_count`` after bump)
    rows: np.ndarray         # (nb, words) arrival bursts since prior tick
    qids: np.ndarray         # (nb,) int32 queue id per burst row
    pops: list               # [(rows, ts)] per queue, host-mirror copies
    counts: list             # rows popped per queue


@functools.partial(
    jax.jit,
    static_argnames=("capacity", "width", "num_slots", "audit", "has_eps"),
    donate_argnums=(0,))
def _run_window(rings, bank, eps_params, xs, suffix, suffix_es, gid, *,
                capacity, width, num_slots, audit, has_eps):
    """The compiled megastep: scan the staged window's ring traffic,
    then run the whole window's forwarding math as one batch.

    ``rings`` is donated — the multi-queue ring buffer mutates in place
    across flushes.  ``eps_params`` is the stacked epoch-delta param
    queue (appended behind ``bank`` as the extended bank); ``xs.es``
    carries each row's extended-bank index so mid-window swaps resolve
    per row.  ``suffix``/``suffix_es``/``gid`` are the host-verified
    payload-suffix dedup table and per-row group ids; padded scan steps
    (``bt == 0``) and padded batch rows are masked by ``pvalid``.
    """
    num_queues = rings["head"].shape[0]
    k = num_slots
    if has_eps:
        bankx = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b]), bank, eps_params)
    else:
        bankx = bank

    def body(rings, x):
        if x["rows"].shape[0]:
            rings = ring_lib.device_push(rings, x["rows"], x["qids"],
                                         x["count"], capacity=capacity)
        # non-audit rings are already slimmed to the key columns, so the
        # pop is a full-row gather either way
        rings, popped, qq, pvalid, n = ring_lib.device_pop(
            rings, x["bt"], width, capacity=capacity)
        return rings, dict(rows=popped, qq=qq, pvalid=pvalid, n=n)

    rings, ys = jax.lax.scan(body, rings, xs)
    t_win = ys["qq"].shape[0]
    rows = ys["rows"].reshape(t_win * width, -1)
    qq = ys["qq"].reshape(-1)
    pvalid = ys["pvalid"].reshape(-1)
    es = xs["es"].reshape(-1)
    gid = gid.reshape(-1)
    if audit:
        slotw = rows[:, pkt.SLOT_WORD]
        ctrl = rows[:, pkt.CONTROL_WORD_LO]
        w0 = rows[:, pkt.META_WORDS]
    else:
        slotw, ctrl, w0 = rows[:, 0], rows[:, 1], rows[:, 2]
    slots = jnp.clip(slotw.astype(jnp.int32), 0, k - 1)

    # one batched forward for the whole window, suffix part deduplicated;
    # integer mismatch counts are split exactly: word 0 per row + shared
    # suffix per (suffix, extended-slot) group
    w1x, b1x, w2x, b2x = bankx["w1p"], bankx["b1"], bankx["w2"], bankx["b2"]
    d = w1x.shape[-1] * 32
    suf_mism = _refk.popcount32(
        suffix[:, None, :] ^ w1x[:, :, 1:][suffix_es]).sum(axis=-1)  # (U, H)
    mism0 = _refk.popcount32(w0[:, None] ^ w1x[:, :, 0][es])         # (N, H)
    mism = mism0 + suf_mism[gid]
    pre = (jnp.int32(d) - 2 * mism).astype(jnp.float32) + b1x[es]
    h = jnp.where(pre >= 0, 1.0, -1.0)
    y = _refk.dense_pm1(h, w2x[es], b2x[es])
    verd = y[:, 0] > 0.0
    acts = _fusedk.actions_ref(y, ctrl)

    wrong = jnp.int32(0)
    if audit:
        # exact reference: full per-row popcount against the same
        # extended-bank entry — no suffix sharing, no dedup table
        payload = rows[:, pkt.META_WORDS:]
        mism_e = _refk.popcount32(payload[:, None, :] ^ w1x[es]).sum(axis=-1)
        pre_e = (jnp.int32(d) - 2 * mism_e).astype(jnp.float32) + b1x[es]
        h_e = jnp.where(pre_e >= 0, 1.0, -1.0)
        y_e = _refk.dense_pm1(h_e, w2x[es], b2x[es])
        wrong = (((y_e[:, 0] > 0.0) != verd) & pvalid).sum(dtype=jnp.int32)

    pv = pvalid.astype(jnp.int32)
    ctr = dict(
        completed=ys["n"].sum(axis=0),
        served=(ys["n"] > 0).astype(jnp.int32).sum(axis=0),
        per_slot=jnp.zeros((num_queues, k), jnp.int32).at[qq, slots].add(pv),
        per_slot_mal=jnp.zeros((num_queues, k), jnp.int32).at[qq, slots].add(
            pv * verd.astype(jnp.int32)),
        actions=jnp.zeros((num_queues, 3), jnp.int32).at[qq, acts].add(pv),
        wrong=wrong,
    )
    ys_out = dict(verdicts=verd.reshape(t_win, width),
                  slots=slots.reshape(t_win, width),
                  actions=acts.reshape(t_win, width))
    return rings, ctr, ys_out


@functools.partial(jax.jit, static_argnames=("capacity",),
                   donate_argnums=(0,))
def _push_trailing(rings, rows, qids, count, *, capacity):
    """Push bursts staged after the window's last tick (flush with no
    following ``tick()`` — e.g. an audit right after a dispatch)."""
    return ring_lib.device_push(rings, rows, qids, count, capacity=capacity)


class MegastepEngine:
    """Deferred-execution engine behind ``DataplaneRuntime``.

    ``dispatch()``/``tick()`` stage work (and run the authoritative host
    ring simulation); ``flush()`` replays the window on device in one
    compiled program and drains results to telemetry, taps, and the
    trace recorder.  Flush triggers: the window reaching
    ``megastep_ticks`` staged ticks, ``retire_all()``, or an epoch
    batch that would overflow the bounded delta queue.
    """

    def __init__(self, runtime):
        rt = runtime
        self.rt = rt
        self.window = rt.megastep_ticks
        self.capacity = rt.rings[0].capacity
        self.words = rt.rings[0]._buf.shape[1]
        # Non-audit windows move only the key columns through the device
        # rings — the batched forward reads everything else from the
        # deduplicated suffix table — so the ring buffer and every staged
        # transfer shrink from 272 words/row to 3.  Audit windows keep
        # full rows: the exact re-score needs the whole payload on device.
        self.dev_words = self.words if rt.audit else len(_KEY_COLS)
        self.dev_rings = ring_lib.device_rings(
            rt.num_queues, self.capacity, packet_words=self.dev_words)
        self._reta_cache = None
        self.dev_reta = None
        self._sync_reta()
        self._steps: list[_Staged] = []
        self._pend_rows: list[np.ndarray] = []
        self._pend_qids: list[np.ndarray] = []
        self._deltas: list = []          # [(seq, DeviceDelta)]
        self._seq = 0
        self._window_bank = None         # bank version at window start
        self._window_pin = None          # donation pin on that buffer

    # -- staging (the runtime's dispatch/tick edge) --------------------------

    def stage_burst(self, rows: np.ndarray, qids: np.ndarray) -> None:
        """Record one routed arrival burst; the host rings already
        admitted it — the device replays the identical admission."""
        if rows.shape[0] == 0:
            return
        self._open_window()
        rows = np.asarray(rows, np.uint32)
        self._pend_rows.append(rows.copy() if self.rt.audit
                               else rows[:, list(_KEY_COLS)])
        self._pend_qids.append(np.asarray(qids, np.int32).copy())

    def stage_tick(self) -> int:
        """Stage one tick: pop the host mirror (authoritative counters /
        timestamps / FIFO order) and defer the device work.  Ticks that
        move no rows and carry no pending burst cost nothing — they are
        never staged, so drain loops do not pad the scan."""
        rt = self.rt
        popped = [ring.pop(rt.batch) for ring in rt.rings]
        counts = [rows.shape[0] for rows, _ in popped]
        total = sum(counts)
        if total == 0 and not self._pend_rows:
            return 0
        self._open_window()
        if self._pend_rows:
            rows = np.concatenate(self._pend_rows)
            qids = np.concatenate(self._pend_qids)
            self._pend_rows, self._pend_qids = [], []
        else:
            rows = np.zeros((0, self.dev_words), np.uint32)
            qids = np.zeros(0, np.int32)
        self._steps.append(_Staged(tick=rt._tick_count, rows=rows,
                                   qids=qids, pops=popped, counts=counts))
        if len(self._steps) >= self.window:
            self.flush()
        return total

    def prepare_epochs(self, n_commands: int) -> None:
        """Make room in the bounded device delta queue *before* an epoch
        batch applies, so a flush never lands mid-transaction."""
        if self._deltas and len(self._deltas) + n_commands > EPOCH_CAPACITY:
            self.flush()

    def stage_delta(self, cmd) -> None:
        """Serialize one just-applied command for the device epoch queue
        (called from ``_apply_command`` inside the epoch transaction)."""
        d = serialize_device_delta(cmd, step=len(self._steps),
                                   runtime=self.rt,
                                   reta_size=DEVICE_RETA_SIZE)
        if d is None:
            return
        if self._window_bank is None:
            # empty window: the next window re-feeds the (already
            # mutated) host bank, so only the RETA mirror needs syncing
            if d.kind == DELTA_RETA:
                self._sync_reta()
            return
        self._seq += 1
        self._deltas.append((self._seq, d))

    def delta_mark(self) -> int:
        """Rollback cookie for ``_control_state`` snapshots."""
        return self._seq

    def delta_rollback(self, mark: int) -> None:
        """Drop deltas staged after ``mark`` — a rolled-back epoch never
        reaches the device."""
        self._deltas = [(s, d) for s, d in self._deltas if s <= mark]

    def staged_rows(self) -> list[int]:
        """Popped-but-unflushed rows per queue (conservation in_flight)."""
        out = [0] * self.rt.num_queues
        for st in self._steps:
            for q, n in enumerate(st.counts):
                out[q] += n
        return out

    def _open_window(self) -> None:
        if self._window_bank is None:
            self._window_bank = self.rt.bank
            # pin the active buffer: a mid-window epoch flip would make it
            # the staging shadow, and staging donates unpinned buffers —
            # the window must keep computing against its opening version
            self._window_pin = self.rt.bank_pin()

    def _sync_reta(self) -> None:
        """Refresh the decorative device RETA mirror iff the host table
        changed (direct ``_install_reta`` callers bypass the deltas)."""
        table = np.asarray(self.rt.reta, np.int32)
        if self._reta_cache is not None and \
                np.array_equal(table, self._reta_cache):
            return
        self._reta_cache = table.copy()
        out = np.full(DEVICE_RETA_SIZE, -1, np.int32)
        n = min(DEVICE_RETA_SIZE, table.shape[0])
        out[:n] = table[:n]
        self.dev_reta = jnp.asarray(out)

    # -- flush ---------------------------------------------------------------

    def flush(self) -> None:
        """Run the staged window on device and drain everything host-side
        (one ``dp.flush`` span of the runtime's ``HostSpans``)."""
        with self.rt.spans.span("dp.flush"):
            self._flush()

    def _flush(self) -> None:
        rt = self.rt
        steps, self._steps = self._steps, []
        deltas = [d for _, d in self._deltas]
        self._deltas = []
        if not steps:
            # queued deltas only exist alongside staged steps; with the
            # window empty the host mirrors already carry every epoch
            self._flush_trailing()
            self._close_window()
            return

        t_pad = min(_round_up(len(steps), _TICK_GRAIN), self.window)
        words = self.words
        k = rt.num_slots
        bmax = max(st.rows.shape[0] for st in steps)
        bmax = _round_up(bmax, _BURST_GRAIN) if bmax else 0
        width = max(_WIDTH_GRAIN,
                    _round_up(max(sum(st.counts) for st in steps),
                              _WIDTH_GRAIN))

        # per-step extended-bank view: cur[s] is the extended index of
        # slot s's live params (base bank, or K + delta index after a
        # mid-window SwapSlot)
        cur = np.arange(k, dtype=np.int32)
        cur_by_step = np.empty((len(steps), k), np.int32)
        di = 0
        for t in range(len(steps)):
            while di < len(deltas) and deltas[di].step <= t:
                if deltas[di].kind == DELTA_SWAP:
                    cur[deltas[di].slot] = k + di
                di += 1
            cur_by_step[t] = cur
        has_eps = any(d.kind == DELTA_SWAP for d in deltas)

        # exact suffix dedup over the window's popped rows, in device
        # compaction order (queue-major within each step)
        meta = pkt.META_WORDS
        chunks, es_chunks = [], []
        for t, st in enumerate(steps):
            cv = cur_by_step[t]
            for q in range(rt.num_queues):
                r = st.pops[q][0]
                if r.shape[0]:
                    chunks.append(r)
                    sl = np.clip(r[:, pkt.SLOT_WORD].astype(np.int64),
                                 0, k - 1)
                    es_chunks.append(cv[sl])
        gid_np = np.zeros((t_pad, width), np.int32)
        es_np = np.zeros((t_pad, width), np.int32)
        if chunks:
            allrows = np.concatenate(chunks)
            es_all = np.concatenate(es_chunks)
            suffix_all = allrows[:, meta + 1:]
            hsh = suffix_all[:, _HASH_COLS].astype(np.float64) @ _HASH_VEC \
                + es_all * _HASH_ES
            _, rep, inv = np.unique(hsh, return_index=True,
                                    return_inverse=True)
            agree = (es_all == es_all[rep][inv]).all() and \
                (suffix_all == suffix_all[rep[inv]]).all()
            if not agree:  # hash collision: exact lexicographic fallback
                key = np.concatenate(
                    [suffix_all, es_all[:, None].astype(np.uint32)], axis=1)
                _, rep, inv = np.unique(key, axis=0, return_index=True,
                                        return_inverse=True)
            suffix_u = suffix_all[rep]
            suffix_es_u = es_all[rep]
            off = 0
            for t, st in enumerate(steps):
                w_off = 0
                for q in range(rt.num_queues):
                    nq = st.counts[q]
                    if nq:
                        gid_np[t, w_off:w_off + nq] = inv[off:off + nq]
                        es_np[t, w_off:w_off + nq] = es_all[off:off + nq]
                        off += nq
                        w_off += nq
        else:
            suffix_u = np.zeros((0, words - meta - 1), np.uint32)
            suffix_es_u = np.zeros(0, np.int32)
        u_pad = _round_up(max(suffix_u.shape[0], 1), _SUFFIX_GRAIN)
        suffix_pad = np.zeros((u_pad, words - meta - 1), np.uint32)
        suffix_pad[:suffix_u.shape[0]] = suffix_u
        ses_pad = np.zeros(u_pad, np.int32)
        ses_pad[:suffix_es_u.shape[0]] = suffix_es_u

        # np.empty: rows at/beyond ``count`` scatter out-of-bounds in
        # device_push (mode="drop"), so the pad contents never land.
        # Non-audit windows stage only the key columns (dev_words == 3).
        rows = np.empty((t_pad, bmax, self.dev_words), np.uint32)
        qids = np.zeros((t_pad, bmax), np.int32)
        count = np.zeros(t_pad, np.int32)
        bt = np.zeros(t_pad, np.int32)
        for t, st in enumerate(steps):
            nb = st.rows.shape[0]
            rows[t, :nb] = st.rows
            qids[t, :nb] = st.qids
            count[t] = nb
            bt[t] = rt.batch
        xs = dict(rows=jnp.asarray(rows), qids=jnp.asarray(qids),
                  count=jnp.asarray(count), bt=jnp.asarray(bt),
                  es=jnp.asarray(es_np))

        eps_params = None
        if has_eps:
            leaves_t, treedef = jax.tree_util.tree_flatten(
                jax.tree_util.tree_map(
                    lambda l: np.zeros((EPOCH_CAPACITY,) + tuple(l.shape[1:]),
                                       np.asarray(l).dtype),
                    self._window_bank))
            for e, dlt in enumerate(deltas):
                if dlt.kind == DELTA_SWAP:
                    for lt, lp in zip(leaves_t,
                                      jax.tree_util.tree_leaves(dlt.params)):
                        lt[e] = np.asarray(lp)
            eps_params = jax.tree_util.tree_unflatten(
                treedef, [jnp.asarray(l) for l in leaves_t])

        self.dev_rings, ctr, ys = _run_window(
            self.dev_rings, self._window_bank, eps_params, xs,
            jnp.asarray(suffix_pad), jnp.asarray(ses_pad),
            jnp.asarray(gid_np),
            capacity=self.capacity, width=width, num_slots=k,
            audit=rt.audit, has_eps=has_eps)
        self._flush_trailing()
        self._drain(steps, ctr, ys)
        self._close_window()

    def _close_window(self) -> None:
        self.rt.bank_unpin(self._window_pin)
        self._window_pin = None
        self._window_bank = None
        self._sync_reta()

    def _flush_trailing(self) -> None:
        if not self._pend_rows:
            return
        rows = np.concatenate(self._pend_rows)
        qids = np.concatenate(self._pend_qids)
        self._pend_rows, self._pend_qids = [], []
        nb = rows.shape[0]
        pad = _round_up(nb, _BURST_GRAIN)
        prows = np.zeros((pad, rows.shape[1]), np.uint32)
        prows[:nb] = rows
        pqids = np.zeros(pad, np.int32)
        pqids[:nb] = qids
        self.dev_rings = _push_trailing(
            self.dev_rings, jnp.asarray(prows), jnp.asarray(pqids),
            jnp.int32(nb), capacity=self.capacity)

    def _drain(self, steps, ctr, ys) -> None:
        """Once-per-megastep drain to the Python side: bulk counter
        fold, ring completion, obs/deploy taps, trace recorder."""
        rt = self.rt
        ctr = {k: np.asarray(v) for k, v in ctr.items()}
        completed = ctr["completed"]
        host = np.zeros(rt.num_queues, np.int64)
        for st in steps:
            host += np.asarray(st.counts, np.int64)
        if not np.array_equal(completed, host):
            raise RuntimeError(
                f"device ring divergence: device popped {completed.tolist()}"
                f" rows/queue, host mirror {host.tolist()}")
        now = time.perf_counter()
        for q in range(rt.num_queues):
            if not completed[q]:
                continue
            lat = np.concatenate(
                [st.pops[q][1] for st in steps if st.counts[q]])
            rt.telemetry.record_window(
                q, ticks=int(ctr["served"][q]),
                completed=int(completed[q]),
                per_slot_total=ctr["per_slot"][q],
                per_slot_malicious=ctr["per_slot_mal"][q],
                actions=ctr["actions"][q],
                latency_us=(now - lat) * 1e6)
            rt.rings[q].mark_completed(int(completed[q]))
        if rt.audit:
            rt.telemetry.wrong_verdict += int(ctr["wrong"])
        if rt.on_retire is not None or rt._record:
            verd = np.asarray(ys["verdicts"])
            slots = np.asarray(ys["slots"])
            acts = np.asarray(ys["actions"])
            for t, st in enumerate(steps):
                off = 0
                for q, n in enumerate(st.counts):
                    if not n:
                        continue
                    sl = slice(off, off + n)
                    off += n
                    if rt.on_retire is not None:
                        rt.on_retire(q, st.pops[q][0], slots[t, sl],
                                     verd[t, sl], acts[t, sl], st.tick)
                    if rt._record:
                        rt.completed_seq[q].extend(
                            int(s) for s in st.pops[q][0][:, SEQ_WORD])
                        rt.completed_verdicts[q].extend(
                            bool(v) for v in verd[t, sl])
                        rt.completed_slots[q].extend(
                            int(s) for s in slots[t, sl])
        rt.telemetry.touch(now)
        if rt.telemetry.has_sink:
            rt.telemetry.emit_delta(tick=steps[-1].tick, now=now,
                                    depths=[len(r) for r in rt.rings])
