"""Records the small traces that the trace reduction is tested on.

    python chipbench/testdata/record.py --arch starcoder2-3b --model 1 --out DIR
    python chipbench/testdata/record.py --arch nemotron-4-15b --model 4 --out DIR

Serves one small job (2 layers at published widths, batch 2, prompt 64,
4 new tokens) once to compile, then again under the profiler inside a
``chipbench.traced_job`` span, and copies the ``.xplane.pb`` to DIR. Prints
the planes and lines of the trace with a few event names of each, and the
reduction's reading, so that a reader can check both by hand.
"""
import json
import shutil
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

import jax  # noqa: E402
from jax.profiler import ProfileData  # noqa: E402

from chipbench import trace as trace_lib  # noqa: E402
from chipbench.harness import TRACED_SPAN  # noqa: E402
from repro.launch.mesh import make_device_mesh  # noqa: E402
from repro.launch.serve import serve  # noqa: E402


def main() -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    mesh = make_device_mesh(args.model)
    kw = dict(layers=2, batch=2, prompt_len=64, gen=4, seed=5, mesh=mesh)
    jax.block_until_ready(serve(args.arch, **kw).logits)
    tmp = Path(args.out) / "raw"
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    with jax.profiler.TraceAnnotation(TRACED_SPAN):
        jax.block_until_ready(serve(args.arch, **kw).logits)
    jax.profiler.stop_trace()
    src = next(tmp.rglob("*.xplane.pb"))
    dst = Path(args.out) / f"{args.arch}-model{args.model}.xplane.pb"
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    pd = ProfileData.from_file(str(dst))
    for plane in pd.planes:
        print("plane", plane.name)
        for ln in plane.lines:
            evs = list(ln.events)
            print("   line", repr(ln.name), len(evs),
                  sorted({e.name for e in evs})[:12])
            if evs and ln.name in (trace_lib.OPS_LINE, trace_lib.MODULES_LINE):
                print("      stats", [(k, v) for k, v in evs[0].stats][:12])
    tr = trace_lib.reduce(pd, TRACED_SPAN)
    print(json.dumps({
        "bytes": dst.stat().st_size, "window_s": tr.window_s,
        "busy_s": [tr.busy_s(d) for d in tr.devices],
        "modules": sorted({m.name for d in tr.devices for m in d.modules}),
        "top_ops": trace_lib.top_ops(tr, 5),
        "idle_gaps": trace_lib.idle_gaps(tr, tr.devices[0], 5, skip=(TRACED_SPAN,)),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
