"""Where `ops/kernels.py compact()`'s rule comes from: compacting W
64-bit columns by one scatter a column over the input's capacity (what
`compact` did up to PR 33) against finding the surviving rows' source
index once and gathering W columns at the output's size, with the index
made by one int32 scatter or by `searchsorted` over the mask's cumsum.
Device seconds a call, read from the profiler's trace.

    python chip_compact_sweep.py            # on the chip; table to stdout
    JAX_PLATFORMS=cpu python chip_compact_sweep.py --rehearse \
        --caps 20096 --widths 0,2

Exits non-zero unless it ran on a TPU or was told `--rehearse` (whose
table has no device seconds: a CPU time is never a device metric). Every
candidate's answer is compared with numpy's on the host.
"""

import argparse
import glob
import json
import os
import re
import sys
import tempfile

import numpy as np

METHODS = ("scatter", "idx_scatter", "idx_search")


def candidates(jnp):
    """The three ways to compact, each `(mask, cols, out_cap) -> (cols,
    n)`; with no column the index methods return the index itself."""

    def _idx(mask, out_cap):
        return jnp.where(mask, jnp.cumsum(mask) - 1, out_cap)

    def scatter(mask, cols, out_cap):
        idx = _idx(mask, out_cap)
        return tuple(jnp.zeros((out_cap,), c.dtype).at[idx].set(
            c, mode="drop") for c in cols), jnp.sum(mask)

    def _gathered(src, n, cols, out_cap):
        keep = jnp.arange(out_cap) < n
        if not cols:
            return (src,), n
        return tuple(jnp.where(keep, c[src], 0) for c in cols), n

    def idx_scatter(mask, cols, out_cap):
        cap = mask.shape[0]
        src = jnp.zeros((out_cap,), jnp.int32).at[_idx(mask, out_cap)].set(
            jnp.arange(cap, dtype=jnp.int32), mode="drop")
        return _gathered(src, jnp.sum(mask), cols, out_cap)

    def idx_search(mask, cols, out_cap):
        cs = jnp.cumsum(mask.astype(jnp.int32))
        src = jnp.searchsorted(cs, jnp.arange(1, out_cap + 1,
                                              dtype=jnp.int32),
                               side="left").astype(jnp.int32)
        src = jnp.minimum(src, mask.shape[0] - 1)
        return _gathered(src, cs[-1], cols, out_cap)

    return {"scatter": scatter, "idx_scatter": idx_scatter,
            "idx_search": idx_search}


def module_events(log_dir):
    """(program, seconds) of every execution on device 0 in the order
    they ran, from the `XLA Modules` line of the trace under `log_dir`;
    [] where the trace has no TPU plane."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    evs = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                evs += [(ev.start_ns, re.sub(r"\(\d+\)$", "", ev.name),
                         ev.duration_ns / 1e9) for ev in line.events]
    return [(name, s) for _, name, s in sorted(evs)]


def host_answer(mask, cols, out_cap):
    keep = np.flatnonzero(mask)[:out_cap]
    outs = []
    for c in cols:
        o = np.zeros(out_cap, c.dtype)
        o[:len(keep)] = c[keep]
        outs.append(o)
    return outs, keep


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--caps", default="164352,1500032,3000064,3844920")
    ap.add_argument("--widths", default="0,1,2,8,11")
    ap.add_argument("--shares", default="0.05,0.2,1.0")
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default="chiprun_out/compact_sweep.json")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import bodo_tpu  # noqa: F401 - x64 on, as the engine runs
    from bodo_tpu.table.table import round_capacity
    from bodo_tpu.utils.kernel_cache import named_jit

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.rehearse:
        sys.exit(f"no TPU here (platform {platform}): --rehearse to "
                 "drive the paths without device seconds")
    cands = candidates(jnp)
    widths = [int(w) for w in args.widths.split(",")]
    shares = [float(s) for s in args.shares.split(",")]
    rng = np.random.default_rng(args.seed)
    table = []
    for cap in (int(c) for c in args.caps.split(",")):
        # columns alternate float64 and int64, as a join's output does;
        # the floats are float32's, which the chip's float-float float64
        # holds exactly, so that equality with the host is the check
        cols = [rng.uniform(0.0, 1e5, cap).astype(np.float32).astype(
                    np.float64) if i % 2 == 0 else
                rng.integers(0, 1 << 40, cap) for i in range(max(widths))]
        dcols = jax.device_put(cols)
        masks = {s: rng.random(cap) < s for s in shares}
        dmasks = jax.device_put(masks)
        rows, calls, fns = [], [], {}
        for share, mask in masks.items():
            count = int(mask.sum())
            for fit in (False, True):
                out_cap = round_capacity(count) if fit else cap
                if fit and out_cap >= cap:
                    continue
                for w in widths:
                    want, keep = host_answer(mask, cols[:w], out_cap)
                    for m in METHODS:
                        if m == "scatter" and w == 0:
                            continue
                        name = f"cs_{m}_{cap}_{w}_{out_cap}"
                        fn = fns.get(name)
                        if fn is None:
                            fn = fns[name] = named_jit(
                                name, lambda mk, cs, _f=cands[m],
                                _o=out_cap: _f(mk, cs, _o))
                        got, n = jax.block_until_ready(
                            fn(dmasks[share], tuple(dcols[:w])))
                        assert int(n) == count, (name, int(n), count)
                        if w == 0:
                            assert np.array_equal(
                                np.asarray(got[0])[:len(keep)], keep), name
                        # the first and the last column: reading eleven
                        # back a call would be most of the sweep's time
                        for i in {0, w - 1} if w else ():
                            assert np.array_equal(np.asarray(got[i]),
                                                  want[i]), name
                        rows.append({"cap": cap, "share": share,
                                     "count": count, "out_cap": out_cap,
                                     "width": w, "method": m, "name": name})
                        calls.append((fn, dmasks[share], tuple(dcols[:w])))
        with tempfile.TemporaryDirectory() as log_dir:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=options)
            try:
                for fn, mk, cs in calls:
                    for _ in range(args.reps):
                        jax.block_until_ready(fn(mk, cs))
            finally:
                jax.profiler.stop_trace()
            evs = module_events(log_dir)
        # one program serves every share at out_cap == cap, so seconds
        # are taken by the order of the calls, not by the program's name
        for i, r in enumerate(rows):
            mine = evs[i * args.reps:(i + 1) * args.reps]
            r["device_s"] = None
            if len(evs) == len(rows) * args.reps:
                assert all(n == "jit_" + r["name"] for n, _ in mine), r
                r["device_s"] = sum(s for _, s in mine) / args.reps
            print(json.dumps(r), flush=True)
        table.extend(rows)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"platform": platform,
                   "device_kind": jax.devices()[0].device_kind,
                   "rehearsal": platform != "tpu", "table": table}, f,
                  indent=1)
    print(f"platform {platform}; table at {args.out}")


if __name__ == "__main__":
    main()
