"""Multi-host data parallelism: 2 cooperating processes (the cross-host
sibling of the 8-virtual-device dryrun) must produce the single-process
loss trajectory on a deterministic batch stream."""

import json
import os
import subprocess
import sys

import numpy as np


def _run(cmd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env.update(extra_env or {})
    r = subprocess.run(
        cmd, env=env, capture_output=True, text=True, timeout=560
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    for line in r.stdout.splitlines():
        if line.startswith("{"):
            return json.loads(line)
    raise AssertionError(f"no JSON line in output: {r.stdout[-500:]}")


def _single_inprocess(argv):
    """The 1-process baseline leg runs IN-PROCESS (the losses are
    device-count independent by design — exactly what these tests assert —
    so the pytest process's 8-device mesh serves as the single-process
    run, saving a cold python+jax startup per test)."""
    from euler_tpu.examples import run_multihost

    return run_multihost.worker(run_multihost.build_parser().parse_args(argv))


def test_two_process_matches_single_process():
    mod = "euler_tpu.examples.run_multihost"
    multi = _run(
        [sys.executable, "-m", mod, "--spawn", "2", "--steps", "5",
         "--port", "12391"]
    )["multihost_losses"]
    single = _single_inprocess(["--steps", "5"])
    np.testing.assert_allclose(multi, single, rtol=1e-4, atol=1e-5)
    assert multi[-1] < multi[0]  # it actually trains


def test_multihost_trainers_with_remote_graph_service(tmp_path):
    """The full reference topology in miniature (VERDICT r3 #7,
    dist_tf_euler.sh:2-43 + start_service.py:70-80): 2 jax.distributed
    trainer processes pull LEAN one-RPC minibatches from 2 GraphService
    processes, and the loss trajectory matches a 1-process trainer
    replaying the same slotted global stream against the same servers."""
    from euler_tpu.datasets.synthetic import random_graph
    from euler_tpu.distributed import Registry
    from euler_tpu.graph import format as tformat

    # sharded on-disk graph the services serve and trainers bootstrap
    # their feature cache from
    g = random_graph(
        num_nodes=400, out_degree=6, feat_dim=8, num_partitions=2, seed=0
    )
    data = str(tmp_path / "data")
    os.makedirs(data, exist_ok=True)
    for p, sh in enumerate(g.shards):
        tformat.write_arrays(os.path.join(data, f"part_{p}"), sh.arrays)
    g.meta.save(data)
    reg = str(tmp_path / "reg")

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    servers = [
        subprocess.Popen(
            [sys.executable, "-m", "euler_tpu.distributed.service",
             "--data", data, "--shard", str(i), "--registry", reg,
             "--no-native"],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )
        for i in range(2)
    ]
    try:
        Registry(reg).wait_for(2, timeout=60.0)
        mod = "euler_tpu.examples.run_multihost"
        common = ["--steps", "4", "--batch", "32", "--remote-data", data,
                  "--remote-registry", reg, "--remote-shards", "2",
                  "--slots", "2"]
        multi = _run(
            [sys.executable, "-m", mod, "--spawn", "2",
             "--port", "12394", *common]
        )["multihost_losses"]
        single = _single_inprocess(common)
        np.testing.assert_allclose(multi, single, rtol=1e-4, atol=1e-5)
        assert multi[-1] < multi[0]  # it actually trains
    finally:
        for p in servers:
            p.kill()
        for p in servers:
            p.wait(timeout=10)
