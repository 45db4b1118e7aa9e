"""Multi-host DCN data parallelism: 2 processes x 4 virtual CPU devices
== 1 process x 8 devices.

The reference's equivalent test tier is BaseSparkTest's local[N] Spark
context (SURVEY §4 "distributed-without-a-cluster"); here the two workers
are REAL separate processes joined by jax.distributed over localhost, so
the cross-process collective path (DCN analog) is genuinely exercised.
"""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_two_process_equals_single_process(tmp_path):
    # baseline: this process already runs an 8-device CPU platform
    from tests.multihost_common import build_net, global_data
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import ExistingDataSetIterator
    from deeplearning4j_tpu.parallel import ParallelWrapper, data_parallel_mesh

    x, y = global_data()
    net1 = build_net()
    dss = [DataSet(x[i:i + 16], y[i:i + 16]) for i in range(0, 32, 16)]
    ParallelWrapper(net1, data_parallel_mesh()).fit(
        ExistingDataSetIterator(dss), epochs=2, async_prefetch=False)

    # two real processes, 4 virtual devices each, same global math
    port = _free_port()
    coordinator = f"127.0.0.1:{port}"
    out = str(tmp_path / "p0.npz")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    script = os.path.join(REPO, "tests", "multihost_worker.py")
    procs = [
        subprocess.Popen(
            [sys.executable, script, coordinator, "2", str(i), out],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for i in range(2)
    ]
    for i, p in enumerate(procs):
        try:
            _, err = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail(f"worker {i} timed out")
        assert p.returncode == 0, f"worker {i} failed:\n{err[-3000:]}"

    got = np.load(out)
    for i, p in enumerate(net1.params_list):
        for k, v in p.items():
            np.testing.assert_allclose(
                got[f"{i}/{k}"], np.asarray(v), rtol=2e-5, atol=2e-6,
                err_msg=f"param {i}/{k} diverged across the process boundary")
