"""Record the small device trace that the trace-reduction test reads.

    python3 chipbench/record_trace.py OUT.xplane.pb [--dump]
    python3 chipbench/record_trace.py OUT.xplane.pb --cell NAME SEED SECONDS

Runs, on the default device, a matmul, the transducer lattice kernel and
the batched Gram kernel at small shapes, each call wrapped in one of the
benchmark's host annotations, with a host sleep between two dispatches so
that the trace holds a known idle gap.  Copies the profiler's
``.xplane.pb`` to OUT and, with ``--dump``, prints every plane and line
with its first events.  With ``--cell`` it instead makes one traced run of
that benchmark cell, keeps its trace at OUT and prints, per line of each
plane, the operations that took most time, and every kernel or custom
call, to find the names the per-layer readers match.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

import jax                                                   # noqa: E402
from jax.profiler import ProfileData, TraceAnnotation        # noqa: E402

from repro.kernels.omp_gram.ops import omp_gram_batched_op   # noqa: E402
from repro.kernels.rnnt_lattice.ops import rnnt_lattice_op   # noqa: E402

#: op names listed in full by ``--cell``: Pallas kernels and custom calls
KERNELS = re.compile(r"lattice|pallas|custom")


def dump_cell(out, name, seed, seconds):
    import collections
    import json
    import traceback
    sys.path.insert(0, os.path.dirname(HERE))
    from chipbench.bench import run
    try:
        print(json.dumps(run(name, int(seed), float(seconds), True,
                             t_start=time.perf_counter(), keep_trace=out)))
    except Exception:
        traceback.print_exc()
    print(f"trace {os.path.getsize(out)} bytes")
    for pl in ProfileData.from_file(out).planes:
        print("PLANE", pl.name)
        for ln in pl.lines:
            tot, cnt, stat = collections.Counter(), collections.Counter(), {}
            for ev in ln.events:
                tot[ev.name] += ev.duration_ns
                cnt[ev.name] += 1
                if ev.name not in stat:
                    stat[ev.name] = (ev.start_ns, dict(ev.stats))
            print("  LINE", repr(ln.name), sum(cnt.values()), "events")
            for n, t in tot.most_common(25):
                print(f"    {t / 1e6:12.3f} ms {cnt[n]:7d}x {n[:90]!r} "
                      f"{str(stat[n])[:300]}")
            for n in sorted(tot):
                if KERNELS.search(n):
                    print(f"    kernel {tot[n] / 1e6:12.3f} ms "
                          f"{cnt[n]:7d}x {n[:200]!r}")


def main(argv):
    if "--cell" in argv:
        i = argv.index("--cell")
        return dump_cell(argv[0], *argv[i + 1:i + 4])
    out, dump = argv[0], "--dump" in argv
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(k[0], (1024, 1024))
    m, a, e = (jax.random.normal(kk, (64, 8, 128)) for kk in k[1:])
    e = e.at[:, :, 0].set(-1e30)
    g = jax.random.normal(k[3], (2, 256, 512))
    mm = jax.jit(lambda x: x @ x)
    lat = jax.jit(rnnt_lattice_op)
    gram = jax.jit(lambda g: omp_gram_batched_op(g, impl="auto"))
    for f, args in ((mm, (x,)), (lat, (m, a, e)), (gram, (g,))):
        jax.block_until_ready(f(*args))
    tmp = tempfile.mkdtemp(dir=os.environ.get("TMPDIR"))
    jax.profiler.start_trace(tmp)
    with TraceAnnotation("bench.window"):
        # host and device clocks in a trace agree to about a millisecond:
        # the sleeps keep every device op well inside the window
        time.sleep(0.005)
        with TraceAnnotation("bench.dispatch"):
            y = mm(x)
        with TraceAnnotation("bench.fetch"):
            jax.block_until_ready(y)
        with TraceAnnotation("bench.plan"):
            time.sleep(0.02)
        with TraceAnnotation("bench.dispatch"):
            r = lat(m, a, e)
            s = gram(g)
        with TraceAnnotation("bench.fetch"):
            jax.block_until_ready((r, s))
        time.sleep(0.005)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    shutil.copyfile(src, out)
    shutil.rmtree(tmp)
    if dump:
        for pl in ProfileData.from_file(out).planes:
            print("PLANE", pl.name)
            for ln in pl.lines:
                evs = list(ln.events)
                print("  LINE", repr(ln.name), len(evs))
                for ev in evs[:6]:
                    print("    ", repr(ev.name), ev.start_ns, ev.duration_ns,
                          dict(ev.stats))
    print(f"wrote {out} ({os.path.getsize(out)} bytes) on "
          f"{jax.devices()[0].device_kind}")


if __name__ == "__main__":
    main(sys.argv[1:])
