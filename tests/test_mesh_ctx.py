"""Mesh detection (``repro.sharding.ctx``) on 4 virtual CPU devices, in a
subprocess (the device count must be set before jax initialises).

The engine enters its mesh with ``jax.set_mesh`` and ``ctx`` reads it
back through the public ``jax.sharding.get_abstract_mesh``: inside the
engine's mesh the client axis is 4 wide, the stacked client params are
split 4 ways, and the pod round pre-reduces the client axis with an
all-reduce (``client_reduce="auto"`` saw the sharded axis)."""
import json
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, jax, jax.numpy as jnp
    from repro.launch import train
    from repro.launch.mesh import engine_mesh
    from repro.sharding.ctx import axis_size, constrain_leading

    out = {"outside": axis_size("client")}
    mesh = engine_mesh(4)
    with jax.set_mesh(mesh):
        out["inside"] = axis_size("client")
        stacked = jax.jit(lambda x: constrain_leading(x, "client"))(
            jnp.zeros((4, 16, 8)))
    out["devices"] = len(stacked.sharding.device_set)
    out["shard_rows"] = sorted({s.data.shape[0]
                                for s in stacked.addressable_shards})
    args = train.build_parser().parse_args(
        ["--arch", "minitron-8b", "--pod", "--reduced", "--rounds", "1",
         "--cohorts", "4", "--seq", "16"])
    state, metrics, runner = train.pod_scale(args, train.fl_config(args))
    text = runner.lower_last().compile().as_text()
    out["engine_client_axis"] = dict(runner.mesh.shape)["client"]
    out["all_reduce"] = text.count("all-reduce(")
    print("RESULT " + json.dumps(out))
""")


def test_engine_mesh_shards_the_client_axis_four_ways():
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=480)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, out.stdout
    res = json.loads(line[0][len("RESULT "):])
    assert res["outside"] == 1
    assert res["inside"] == 4
    assert res["devices"] == 4 and res["shard_rows"] == [1]
    assert res["engine_client_axis"] == 4
    assert res["all_reduce"] > 0
