"""Record the small profiler trace that `test_trace.py` reduces.

    python bench/tests/record_trace.py --out <dir>

Runs on the chip only: a jitted step (`jit_decode_step`), one Pallas GEMM
and one flash-attention kernel of the program, each inside a host
`TraceAnnotation`, under `jax.profiler`.  It prints the trace's planes,
lines and events, so that the reduction can be written against what the
profiler really names, and copies the `.xplane.pb` to ``--out`` (committed as
`bench/tests/data/small.xplane.pb`, with the recording machine's source
paths overwritten by a path of the same length).
"""
from __future__ import annotations

import argparse
import glob
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    d = jax.devices()[0]
    print(f"platform={d.platform} device_kind={d.device_kind} "
          f"count={len(jax.devices())}", flush=True)
    if d.platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 1

    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.gemm import gemm
    from repro.kernels.gemm.ops import TileConfig

    k = jax.random.split(jax.random.PRNGKey(0), 6)
    a = jax.random.normal(k[0], (256, 512), jnp.bfloat16)
    b = jax.random.normal(k[1], (512, 1024), jnp.bfloat16)
    q = jax.random.normal(k[2], (2, 4, 256, 128), jnp.bfloat16)
    kk = jax.random.normal(k[3], (2, 4, 256, 128), jnp.bfloat16)
    v = jax.random.normal(k[4], (2, 4, 256, 128), jnp.bfloat16)

    def decode_step(x, w):
        return jnp.tanh(x @ w).sum(axis=0)

    step = jax.jit(decode_step)
    tile = TileConfig(128, 256, 256)
    # warm every program outside the trace
    jax.block_until_ready(step(a, b))
    jax.block_until_ready(gemm(a, b, tile=tile))
    jax.block_until_ready(flash_attention(q, kk, v))

    tmp = tempfile.mkdtemp(prefix="trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.traced"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.step"):
                jax.block_until_ready(step(a, b))
            with jax.profiler.TraceAnnotation("bench.gemm"):
                jax.block_until_ready(gemm(a, b, tile=tile))
            with jax.profiler.TraceAnnotation("bench.flash"):
                jax.block_until_ready(flash_attention(q, kk, v))
            time.sleep(0.01)
    jax.profiler.stop_trace()

    path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(path, out / "trace.xplane.pb")
    print(f"trace {path} -> {out} ({Path(path).stat().st_size} B)")

    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines={len(lines)}")
        for line in lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r} events={len(evs)}")
            for ev in evs[:12]:
                stats = {kk: vv for kk, vv in ev.stats}
                print(f"    EV {ev.name!r} start={ev.start_ns} "
                      f"dur={ev.duration_ns} stats={str(stats)[:300]}")
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
