"""Record the served-decode trace that `test_serve_trace.py` reduces.

    python bench/tests/record_serve_trace.py --out <dir>

Runs on the chip only.  A tiny model (the program's reduced qwen3-14b,
`data/tiny-dense.json`) is served by `greedy_decode` with the shadow
runtime, as the `serve_batch` driver serves a cell, for a few decode
steps inside the `bench.traced` span under `jax.profiler`.  It writes the
trace as ``serve.xplane.pb`` and the decode program's compiled text, from
`attribution.decode_program_text`, as ``serve_decode.hlo.txt``, both with
the recording checkout's path overwritten by a path of the same length.
It prints the host spans and the decode program's operations with their
scopes, so that the readers can be checked against what the chip names.
"""
from __future__ import annotations

import argparse
import glob
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TRAFFIC = {"driver": "serve_batch", "batch": 4, "prompt_len": 16,
           "out_len": {"median": 6, "sigma": 0.8, "min": 2, "max": 8}, "runtime": "shadow"}
STEPS = 6


def scrub(data: bytes) -> bytes:
    """The checkout's path, overwritten by one of the same length."""
    here = str(ROOT).encode() + b"/"
    return data.replace(here, b"/checkout/".ljust(len(here), b"/"))


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
        print("record_serve_trace: no TPU", file=sys.stderr)
        return 1

    from bench import run as R
    from bench.lib import attribution, trace, weights
    from repro.configs import get_arch, list_archs
    from repro.configs.base import register
    from repro.runtime import Runtime
    from repro.train.serve_loop import greedy_decode

    config = R.load_json(ROOT / "bench" / "tests" / "data" / "tiny-dense.json")
    if config["arch"] not in list_archs():
        register(get_arch("qwen3-14b").reduced())
    cell = R.Cell("tiny", {"name": "tiny", "config": "tiny-dense", "traffic": "tiny",
                           "chips": 1}, config, TRAFFIC, {}, {"end_to_end": [], "per_layer": []})
    ctx = R.Ctx(cell, 0, 0.0, True, jax.devices()[:1], {})
    driver = R.load_module("drivers", "serve_batch")
    model, _, shapes, shardings = driver.build(ctx)
    params = weights.make(shapes, 0, shardings)
    runtime = Runtime()
    s_max = TRAFFIC["prompt_len"] + TRAFFIC["out_len"]["max"] + 1
    batch = {"tokens": jnp.asarray(driver.prompts(ctx, 0))}

    def serve():
        return greedy_decode(model, params, batch, s_max=s_max, steps=STEPS,
                             cache_dtype=jnp.bfloat16, runtime=runtime,
                             tenant=config["arch"])

    jax.block_until_ready(serve().tokens)       # compile outside the trace
    tmp = tempfile.mkdtemp(prefix="serve_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.traced"):
        jax.block_until_ready(serve().tokens)
    jax.profiler.stop_trace()

    sds = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                       shapes, shardings)
    text = attribution.decode_program_text(model, sds, TRAFFIC["batch"],
                                           TRAFFIC["prompt_len"], s_max, jnp.bfloat16)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    raw = Path(glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]).read_bytes()
    shutil.rmtree(tmp, ignore_errors=True)
    (out / "serve.xplane.pb").write_bytes(scrub(raw))
    (out / "serve_decode.hlo.txt").write_bytes(scrub(text.encode()))
    print(f"wrote {out} (trace {len(raw)} B, text {len(text)} B)")

    t = trace.load(str(out / "serve.xplane.pb"))
    for h in sorted(t.host, key=lambda e: e.start):
        if h.name.startswith(("serve.", "runtime.", "bench.")):
            print(f"  SPAN {h.name} {h.start:.6f} {h.dur * 1e6:.1f} us")
    scopes = attribution.op_scopes(text)
    for dev, (spans, ops) in attribution.decode_ops(t).items():
        print(f"{dev}: {len(spans)} decode programs, {len(ops)} ops, shares "
              f"{attribution.time_by_region(spans, ops, scopes)}")
        for e in ops[:len(ops) // max(len(spans), 1)]:
            print(f"  OP {e.name} {e.dur * 1e6:.2f} us {scopes.get(e.name, '<missing>')!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
