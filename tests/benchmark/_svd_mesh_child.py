"""Child process of ``test_svd_rand.py``: four virtual CPU devices (the
parent sets ``--xla_force_host_platform_device_count=4``), x64 off, as
the driver runs the benchmark.

First the program alone: ``approximate_svd`` on an operand whose rows
``shard_rows_padded`` spread over ``default_mesh()`` (2x2) against the
same call on one device.  Then the rehearsal of ``svd_rand_1e7_k100_x4``
through the harness's own ``main``, with the entry it builds kept to look
at: its mesh, how A lies on the devices, the result line.  One JSON
object on the last line.
"""

import contextlib
import io
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(REPO, "benchmarks"), REPO]

import run as harness  # noqa: E402

CELL = "svd_rand_1e7_k100_x4"


def sharded_against_one_device():
    import jax.numpy as jnp
    import numpy as np

    from libskylark_tpu import SketchContext
    from libskylark_tpu.linalg import SVDParams, approximate_svd
    from libskylark_tpu.parallel import default_mesh, shard_rows_padded

    rng = np.random.default_rng(3)
    A = jnp.asarray(rng.standard_normal((1024, 6)) @ rng.standard_normal((6, 40))
                    + 0.01 * rng.standard_normal((1024, 40)), jnp.float32)
    mesh = default_mesh()
    As, rows = shard_rows_padded(A, mesh)

    def factor(X):
        return approximate_svd(X, 6, SketchContext(seed=5),
                               SVDParams(num_iterations=2), return_info=True)

    (U1, s1, V1), info1 = factor(A)
    (U4, s4, V4), info4 = factor(As)
    u_shards = sorted(s.data.shape[0] for s in U4.addressable_shards)
    U1, s1, V1, U4, s4, V4 = map(np.asarray, (U1, s1, V1, U4, s4, V4))
    return {
        "mesh": dict(mesh.shape), "rows": rows, "u_shards": u_shards,
        "attempts": [info1["attempts"], info4["attempts"]],
        "sigma": float(np.max(np.abs(s4 - s1)) / s1[0]),
        # the bases may differ by signs: compare what does not
        "left": float(np.linalg.norm((U4 * s4) @ V4.T - (U1 * s1) @ V1.T)
                      / np.linalg.norm(U1 * s1)),
        "right": float(np.linalg.norm(V4 @ V4.T - V1 @ V1.T)),
    }


def rehearsal(seed):
    """The last line of ``run.py --rehearse`` and the entry it drove."""
    kept, real = {}, harness.load_module

    def load(directory, name):
        mod = real(directory, name)
        if directory == "entries":
            class Kept(mod.Entry):
                def setup(self):
                    super().setup()
                    kept["entry"] = self

            mod.Entry = Kept
        return mod

    harness.load_module = load
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            rc = harness.main(["--workload", CELL, "--seed", str(seed),
                               "--seconds", "0.2", "--trace", "0", "--rehearse"])
    finally:
        harness.load_module = real
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    e = kept["entry"]
    return {
        "rc": rc, "mesh": dict(e.mesh.shape), "chips": e.sizes["chips"],
        "rows": e.sizes["rows"],
        "a_shards": sorted(s.data.shape[0] for s in e.A.addressable_shards),
        "a_devices": len({s.device for s in e.A.addressable_shards}),
        **{k: line[k] for k in ("correct", "attempted", "failed", "compared",
                                "metrics", "device")},
    }


if __name__ == "__main__":
    import jax

    jax.config.update("jax_enable_x64", False)
    print(json.dumps({"devices": len(jax.devices()),
                      "program": sharded_against_one_device(),
                      "rehearsal": rehearsal(2**31 + 29)}))
