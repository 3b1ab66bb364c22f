"""GOLDYLOC quickstart: tune → predict → execute concurrent GEMMs.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    ConcurrencyController,
    GemmDesc,
    GemmRequest,
    GOLibrary,
    generate_gemm_pool,
    profile_dataset,
    train_predictor,
)
from repro.kernels.dispatch import interpret_mode


def main():
    lib = GOLibrary()

    # 1) Resource-constrained tuning → GO kernels per concurrency degree.
    d = GemmDesc(4096, 128, 1024, dtype="f32")  # paper Fig. 4's 4k_128_1k
    entry = lib.get(d)
    print(f"GEMM {d.key()}:")
    print(f"  isolated-tuned tile : {entry.isolated.key()}")
    for cd in (2, 4, 8, 16):
        print(f"  GO tile @CD={cd:<2}      : {entry.go[cd].key()} "
              f"(from RC={entry.rc_source[cd]}, "
              f"modeled speedup vs seq {entry.speedup[cd]:.2f}x)")

    # 2) Train the lightweight dynamic predictor (offline, once per chip).
    pool = generate_gemm_pool(256, seed=1)
    X, y = profile_dataset(pool, lib)
    predictor = train_predictor(X, y, epochs=200)
    ctrl = ConcurrencyController(library=lib, predictor=predictor)

    # 3) Dispatch a queue of independent GEMMs through the controller (the
    #    command-processor analogue) — it picks CD and the GO kernels.
    key = jax.random.PRNGKey(0)
    reqs = []
    for i in range(8):
        a = jax.random.normal(jax.random.fold_in(key, i), (256, 192))
        b = jax.random.normal(jax.random.fold_in(key, 99 + i), (192, 128))
        reqs.append(GemmRequest(GemmDesc(256, 128, 192, dtype="f32"), a, b))
    sched = ctrl.plan([r.desc for r in reqs])
    for g in sched.groups:
        print(f"  plan: {g.mode} CD={g.cd} tile={g.tile.key()} "
              f"modeled {g.modeled_time_s * 1e6:.1f} us")
    outs = ctrl.execute(reqs, interpret=interpret_mode())  # real pallas kernels
    ref = reqs[0].a @ reqs[0].b
    np.testing.assert_allclose(outs[0], ref, rtol=2e-4, atol=2e-4)
    print("  executed through grouped pallas kernel: results verified ✓")


if __name__ == "__main__":
    main()
