"""From the profiler's `.xplane.pb` to seconds: the device's busy union,
seconds by program (XLA module) and by operation, and the idle gaps by
what the host was doing. Reads the file with `jax.profiler.ProfileData`
and nothing else. Self-test: `benchmarks/tests/test_selftests.py` on the
recorded trace in `benchmarks/selftest/`.

On a TPU each chip is a plane `/device:TPU:<n>` whose line `XLA Modules`
holds one event per program execution (`jit_join_local(<fingerprint>)`)
and whose line `XLA Ops` holds one per HLO operation. A CPU rehearsal has
no device plane; there the XLA thread-pool lines of `/host:CPU` stand in
(events that carry an `hlo_module`), only so that the rehearsal walks the
same code. The host's spans are the events of `/host:CPU`'s other lines.
"""

import glob
import os
import re

import numpy as np

COLLECTIVE = re.compile(
    r"all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute"
    r"|all_to_all|all_gather|all_reduce|psum|ppermute", re.I)
_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_SUFFIX = re.compile(r"\(\d+\)$")
# a TPU operation event is named by its whole HLO instruction:
# "%fusion.5 = (u32[384]{0:T(1024)}, ...) fusion(u32[384]{...} %x, ...), kind=..."
_HLO = re.compile(r"^(%[\w.\-]+) = ")
_OPCODE = re.compile(r"(?<=[\s)}\]])([a-z][a-z0-9\-]*)\(")


def short_op(name):
    """'%fusion.5 fusion' from an HLO instruction's text; other names
    (a CPU rehearsal's) stay as they are."""
    m = _HLO.match(name)
    if not m:
        return name
    op = _OPCODE.search(name, m.end())
    return m.group(1) + (" " + op.group(1) if op else "")


def self_ns(starts, ends):
    """Each interval's length less what intervals nested in it cover
    (operations of a loop's body are events inside the loop's event)."""
    order = np.lexsort((-ends, starts))
    own = (ends - starts).astype(float)
    stack = []
    for i in order:
        while stack and ends[stack[-1]] <= starts[i]:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(ends[i], ends[stack[-1]]) - starts[i]
        stack.append(i)
    return np.maximum(own, 0.0)
WINDOW_SPAN = "bench:window"


def find_xplane(log_dir):
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def union_ns(starts, ends):
    """Length of the union of intervals, and the merged intervals."""
    if len(starts) == 0:
        return 0.0, np.zeros((0, 2))
    order = np.argsort(starts, kind="stable")
    s, e = np.asarray(starts)[order], np.asarray(ends)[order]
    run_end = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > run_end[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, len(s) - 1)
    merged = np.stack([s[first], run_end[last]], axis=1)
    return float((merged[:, 1] - merged[:, 0]).sum()), merged


def _events(line, keep=lambda ev: True):
    """(names, starts, ends) of a line's events, ns."""
    names, starts, ends = [], [], []
    for ev in line.events:
        if keep(ev):
            names.append(ev.name)
            starts.append(ev.start_ns)
            ends.append(ev.start_ns + ev.duration_ns)
    return names, np.asarray(starts, float), np.asarray(ends, float)


_DEVICE_LINES = {"XLA Ops": "ops", "XLA Modules": "modules",
                 "Async XLA Ops": "async"}


class Reduction:
    """What the readers under layer_metrics/ and the harness read."""

    def __init__(self, path):
        from jax.profiler import ProfileData
        self.path = path
        self.devices = {}   # id -> {"ops"|"modules"|"async": (names, s, e)}
        host_plane = None
        for plane in ProfileData.from_file(path).planes:
            m = _TPU_PLANE.match(plane.name)
            if m:
                dev = self._device_lines(plane)
                if "ops" in dev:
                    self.devices[int(m.group(1))] = dev
            elif plane.name == "/host:CPU":
                host_plane = plane
        self.platform = "tpu" if self.devices else "host-standin"
        host_lines = list(host_plane.lines) if host_plane is not None else []
        if not self.devices:
            self._standin_device(host_lines)
        spans = [_events(line) for line in host_lines
                 if not line.name.startswith("tf_XLA")]
        self.host = (
            np.asarray([n for sp in spans for n in sp[0]], object),
            np.concatenate([sp[1] for sp in spans] or [np.zeros(0)]),
            np.concatenate([sp[2] for sp in spans] or [np.zeros(0)]))
        self._window()
        self.busy, self._merged, self._sums = {}, {}, {}
        for d, dev in self.devices.items():
            ns, self._merged[d] = union_ns(*self._clip(*dev["ops"][1:]))
            self.busy[d] = ns / 1e9

    @staticmethod
    def _device_lines(plane):
        dev = {}
        for line in plane.lines:
            key = _DEVICE_LINES.get(line.name)
            if key:
                names, s, e = _events(line)
                if key != "modules":
                    names = [short_op(n) for n in names]
                dev[key] = (names, s, e)
        return dev

    def _standin_device(self, host_lines):
        """A CPU rehearsal: the XLA thread pools' operation events stand
        in for a device; each carries its module's name."""
        names, mods, ss, ee = [], [], [], []
        for line in host_lines:
            if line.name.startswith("tf_XLA"):
                for ev in line.events:
                    mod = dict(ev.stats).get("hlo_module")
                    if mod is not None:
                        names.append(ev.name)
                        mods.append(mod)
                        ss.append(ev.start_ns)
                        ee.append(ev.start_ns + ev.duration_ns)
        if names:
            s, e = np.asarray(ss, float), np.asarray(ee, float)
            # no module events on the CPU: every op stands for its module
            self.devices[0] = {"ops": (names, s, e), "modules": (mods, s, e)}

    # -------------------------------------------------------------- window
    def _window(self):
        names, s, e = self.host
        at = np.flatnonzero(names == WINDOW_SPAN)
        lo = min((dev["ops"][1].min() for dev in self.devices.values()
                  if len(dev["ops"][1])), default=0.0)
        hi = max((dev["ops"][2].max() for dev in self.devices.values()
                  if len(dev["ops"][2])), default=0.0)
        if len(at):
            w0, w1 = float(s[at[0]]), float(e[at[-1]])
            # the span has to hold the device's events, or the two planes
            # are not on one clock and the span cannot bound them
            if w0 <= lo + 1e6 and w1 >= hi - 1e6 or not self.devices:
                self.window_ns = (w0, w1)
                self.window_from = WINDOW_SPAN
                return
        self.window_ns = (lo, hi)
        self.window_from = "first to last device operation"

    @property
    def window_s(self):
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    def _clip(self, s, e):
        w0, w1 = self.window_ns
        s, e = np.clip(s, w0, w1), np.clip(e, w0, w1)
        keep = e > s
        return s[keep], e[keep]

    # ---------------------------------------------------------------- sums
    def busiest(self):
        return max(self.busy, key=self.busy.get)

    def busy_mean_s(self):
        return float(np.mean(list(self.busy.values()))) if self.busy else 0.0

    def _sum_by_name(self, kind, device):
        names, s, e = self.devices[device][kind]
        out = {}
        for n, a, b in zip(names, s, e):
            n = _SUFFIX.sub("", n)
            out[n] = out.get(n, 0.0) + (b - a) / 1e9
        return out

    def _module_of(self, device, starts):
        """Name of the program running at each time, '' where none."""
        if "modules" not in self.devices[device]:
            return [""] * len(starts)
        names, s, e = self.devices[device]["modules"]
        order = np.argsort(s)
        at = np.searchsorted(s[order], starts, side="right") - 1
        out = []
        for t, j in zip(starts, at):
            k = order[j] if j >= 0 else -1
            out.append(_SUFFIX.sub("", names[k])
                       if k >= 0 and t < e[k] else "")
        return out

    def module_seconds(self, device=None):
        """{program name: seconds} on one device (default the busiest)."""
        device = self.busiest() if device is None else device
        if "modules" not in self.devices[device]:
            return {}
        if ("modules", device) not in self._sums:
            self._sums["modules", device] = self._sum_by_name("modules",
                                                              device)
        return self._sums["modules", device]

    def op_seconds(self, device=None):
        """{'program/operation': seconds of its own}, nested operations
        taken out of the loops that hold them."""
        device = self.busiest() if device is None else device
        if ("ops", device) in self._sums:
            return self._sums["ops", device]
        names, s, e = self.devices[device]["ops"]
        own = self_ns(s, e)
        mods = self._module_of(device, s)
        out = {}
        for n, m, ns in zip(names, mods, own):
            key = f"{m}/{n}" if m and m != n else n
            out[key] = out.get(key, 0.0) + ns / 1e9
        self._sums["ops", device] = out
        return out

    def family_seconds(self, patterns, device=None):
        """Seconds of the programs whose name matches any pattern; None
        where no such program ran (nothing to read)."""
        rx = [re.compile(p) for p in patterns]
        hit = [v for n, v in self.module_seconds(device).items()
               if any(r.search(n) for r in rx)]
        return sum(hit) if hit else None

    def collective_seconds(self, device=None):
        """Seconds in which a collective operation was running: the union
        of their events on the operation lines, synchronous and
        asynchronous; None where there was none."""
        device = self.busiest() if device is None else device
        ss, ee = [], []
        for kind in ("ops", "async"):
            if kind in self.devices[device]:
                names, s, e = self.devices[device][kind]
                hit = np.flatnonzero([bool(COLLECTIVE.search(n))
                                      for n in names])
                ss.append(s[hit])
                ee.append(e[hit])
        ss, ee = np.concatenate(ss), np.concatenate(ee)
        if not len(ss):
            return None
        return union_ns(*self._clip(ss, ee))[0] / 1e9

    def other_seconds(self, all_patterns, device=None):
        rx = [re.compile(p) for p in all_patterns]
        return sum(v for n, v in self.module_seconds(device).items()
                   if not any(r.search(n) for r in rx))

    # ----------------------------------------------------------- breakdown
    def idle_gaps(self, top=10, consider=400):
        """[[what the host was doing, seconds]...]: the idle gaps of the
        busiest device inside the window, each named by the benchmark's
        span that holds its middle and the shortest host event that
        spans the whole gap, summed by that name."""
        if not self.busy:
            return []
        merged = self._merged[self.busiest()]
        w0, w1 = self.window_ns
        edges = np.concatenate([[w0], merged.ravel(), [w1]])
        g0, g1 = edges[0::2], edges[1::2]
        keep = g1 > g0
        g0, g1 = g0[keep], g1[keep]
        order = np.argsort(g0 - g1)[:consider]
        names, hs, he = self.host
        bench = np.flatnonzero([str(n).startswith("bench:")
                                and n != WINDOW_SPAN for n in names])
        out = {}
        for i in order:
            mid = (g0[i] + g1[i]) / 2
            inside = bench[(hs[bench] <= mid) & (he[bench] >= mid)]
            label = str(names[inside[np.argmin(he[inside] - hs[inside])]]) \
                if len(inside) else "bench:(between spans)"
            spans = np.flatnonzero((hs <= g0[i]) & (he >= g1[i]))
            spans = spans[[not str(names[j]).startswith("bench:")
                           for j in spans]]
            if len(spans):
                j = spans[np.argmin(he[spans] - hs[spans])]
                label += " / " + str(names[j])
            out[label] = out.get(label, 0.0) + (g1[i] - g0[i]) / 1e9
        return [[k, v] for k, v in
                sorted(out.items(), key=lambda kv: -kv[1])[:top]]

    def device_ops(self, all_patterns, top=10):
        """[[name, seconds]...]: the programs that took most time, the sum
        of those no family claims, and the operations that took most."""
        mods = sorted(self.module_seconds().items(), key=lambda kv: -kv[1])
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])
        n_mod = min(len(mods), top // 2 - 1)
        out = [["program " + k, v] for k, v in mods[:n_mod]]
        out.append(["programs of no family (other)",
                    self.other_seconds(all_patterns)])
        out += [["op " + k, v] for k, v in ops[:top - len(out)]]
        return out
