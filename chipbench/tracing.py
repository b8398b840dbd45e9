"""Reduce a JAX profiler trace to device busy time, op times and idle gaps.

What a TPU v5e trace holds (read by hand from one recorded on the chip,
``tests/data/tick_trace.xplane.pb``):

* plane ``/device:TPU:<n>``: line ``XLA Modules`` has one event per
  program run (``jit_packet_step(<hash>)``), line ``XLA Ops`` one per HLO
  op inside it; the fused Pallas kernel is the op whose text holds
  ``custom_call_target="tpu_custom_call"`` (named ``%fused_forward.<n>``);
* plane ``/host:CPU``: one line per host thread; the harness's spans
  (``jax.profiler.TraceAnnotation``) sit on the ``python`` line, and the
  runtime marks each program launch with ``tpu::System::Execute``, whose
  ``core_id`` stat is the chip it runs on.

On four chips (``tests/data/tick_trace_4chips.xplane.pb``, 16 queues by
``shard_map``) one launch of the sharded step is four
``tpu::System::Execute`` events, one per chip, each on a
``py_xla_execute`` thread of its own with that chip's ``core_id``, and
four ``XLA Modules`` events, one on each chip's plane.  Copying the
tick's batch from chip 0 into the four shards adds a ``jit__multi_slice``
module on ``/device:TPU:0`` alone, launched from the ``main`` thread with
``core_id`` 0.  So each chip has one launch for each of its modules.

Each chip's timestamps run about a millisecond off the host's, by its own
amount.  A chip runs programs in the order they are launched to it, so
its clock is moved to the host's by the least shift that starts none of
its programs before its launch: ``max(launch_start - module_start)`` over
that chip's launches and modules paired in order.  Where their counts
differ, no shift is made and that chip's ``aligned`` is False.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os

#: Host spans the harness writes, and the one that brackets the window.
SPANS = ("generate", "dispatch", "tick", "submit", "poll")
WINDOW = "window"
LAUNCH = "tpu::System::Execute"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = "/device:TPU:"


@dataclasses.dataclass
class Device:
    """One chip's op and module events, on the host clock (ns)."""
    name: str
    ops: list          # [(start, end, text)]
    modules: list      # [(start, end, name)]
    aligned: bool


@dataclasses.dataclass
class Reduced:
    window: tuple      # (start, end) ns of the harness's window span
    spans: list        # [(start, end, name)] harness spans on the host
    devices: list      # [Device]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        total = sum(_length(_union(_clip(d.ops, self.window)))
                    for d in self.devices)
        return total / len(self.devices) / 1e9

    def op_seconds(self, match, *, within: str | None = None) -> float:
        """Device seconds, summed over chips, of the window's ops whose
        text satisfies ``match``; ``within`` keeps only ops that start
        inside a module whose name contains it."""
        total = 0
        for d in self.devices:
            mods = [(s, e) for s, e, n in d.modules
                    if within is None or within in n]
            for s, e, text in _clip(d.ops, self.window):
                if match(text) and (within is None or _inside(s, mods)):
                    total += e - s
        return total / 1e9

    def top_ops(self, n: int = 10) -> list:
        """[[op, seconds]] of the ops that took most device time."""
        acc = collections.Counter()
        for d in self.devices:
            for s, e, text in _clip(d.ops, self.window):
                acc[text.split(" = ")[0]] += e - s
        return [[k, v / 1e9] for k, v in acc.most_common(n)]

    def idle_by_span(self, n: int = 10) -> list:
        """[[span, seconds]]: the device's idle time in the window, split
        by the harness span the host was in (``other`` where none)."""
        acc = collections.Counter()
        spans = sorted(self.spans)
        for d in self.devices:
            busy = _union(_clip(d.ops, self.window))
            for g0, g1 in _gaps(busy, self.window):
                covered = 0
                for s, e, name in spans:
                    if e <= g0:
                        continue
                    if s >= g1:
                        break
                    ov = min(e, g1) - max(s, g0)
                    if ov > 0:
                        acc[name] += ov
                        covered += ov
                acc["other"] += max(0, (g1 - g0) - covered)
        k = max(1, len(self.devices))
        return [[name, v / k / 1e9] for name, v in acc.most_common(n) if v > 0]


def _clip(events, window):
    w0, w1 = window
    return [(max(s, w0), min(e, w1), t) for s, e, t in events
            if e > w0 and s < w1]


def _union(events) -> list:
    out: list = []
    for s, e, _ in sorted(events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _gaps(busy, window):
    t = window[0]
    for s, e in busy:
        if s > t:
            yield t, s
        t = max(t, e)
    if window[1] > t:
        yield t, window[1]


def _inside(t, intervals) -> bool:
    return any(s <= t < e for s, e in intervals)


def find(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``start_trace`` directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _events(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def reduce(path: str, device_ids=None) -> Reduced:
    """Read one trace file into a ``Reduced``: the planes of the chips
    ``device_ids`` names (``jax.Device.id``), or every chip's where None."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, window = [], None
    launches = collections.defaultdict(list)   # chip -> launch starts
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e, name = ev.start_ns, ev.start_ns + ev.duration_ns, ev.name
                if name in SPANS:
                    spans.append((s, e, name))
                elif name == WINDOW:
                    window = (s, e)
                elif name == LAUNCH:
                    launches[dict(ev.stats).get("core_id")].append(s)
    if window is None:
        raise ValueError(f"trace {path} has no '{WINDOW}' span")
    devices = []
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        chip = int(plane.name[len(DEVICE_PLANE):])
        if device_ids is not None and chip not in device_ids:
            continue
        lines = {line.name: _events(line) for line in plane.lines}
        ops = lines.get(OPS_LINE, [])
        mods = sorted(lines.get(MODULES_LINE, []))
        mine = sorted(launches.get(chip, ()))
        shift, aligned = 0, False
        if mods and len(mods) == len(mine):
            shift = max(l - m[0] for l, m in zip(mine, mods))
            aligned = True
        devices.append(Device(
            name=plane.name,
            ops=[(s + shift, e + shift, t) for s, e, t in ops],
            modules=[(s + shift, e + shift, n) for s, e, n in mods],
            aligned=aligned))
    return Reduced(window=window, spans=spans, devices=devices)
