"""int8 error-feedback compressed psum under shard_map (subprocess, 4 devs)."""

import json
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys, json
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    from repro.launch.mesh import mesh_context
    import numpy as np
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from jax.experimental.shard_map import shard_map
    from repro.parallel.compression import compressed_psum

    mesh = jax.make_mesh((4,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (4, 512)), jnp.float32)

    @partial(shard_map, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
             check_rep=False)
    def f(xs):
        return compressed_psum(xs[0], "data")[None]

    with mesh_context(mesh):
        out = jax.jit(f)(x)
    exact = jnp.sum(x, axis=0)
    # every shard holds the same (compressed) sum
    for i in range(4):
        err = float(jnp.max(jnp.abs(out[i] - exact)))
        rel = err / float(jnp.max(jnp.abs(exact)))
        assert rel < 0.05, (i, rel)
    print(json.dumps({"rel_err": rel}))
""")


def test_compressed_psum(tmp_path):
    script = tmp_path / "cpsum_test.py"
    script.write_text(SCRIPT)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))), env=env,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["rel_err"] < 0.05
