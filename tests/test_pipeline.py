"""GPipe-over-pod-axis: pipeline output must equal sequential execution.
Runs in a subprocess with forced host devices (main process keeps 1)."""

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
    from repro.parallel.pipeline import gpipe_apply, sequential_reference

    mesh = jax.make_mesh((4,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    S, M, MB, D = 4, 6, 3, 8
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(0, 0.5, (S, D, D)), jnp.float32)}
    xs = jnp.asarray(rng.normal(0, 1, (M, MB, D)), jnp.float32)

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"])

    with mesh_context(mesh):
        out = jax.jit(lambda p, x: gpipe_apply(stage_fn, p, x, mesh))(params, xs)
    ref = sequential_reference(stage_fn, params, xs)
    err = float(jnp.max(jnp.abs(out - ref)))
    # the lowered HLO must contain the expected collective-permutes
    with mesh_context(mesh):
        hlo = jax.jit(lambda p, x: gpipe_apply(stage_fn, p, x, mesh)).lower(params, xs).compile().as_text()
    n_cp = hlo.count("collective-permute(")
    print(json.dumps({"err": err, "n_cp": n_cp}))
    assert err < 1e-5, err
""")


def test_gpipe_matches_sequential(tmp_path):
    script = tmp_path / "pipe_test.py"
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
    assert res["err"] < 1e-5
    assert res["n_cp"] >= 1
