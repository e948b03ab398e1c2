"""The four-chip path rehearsed on four virtual CPU devices, through the
whole command: ray_tpu.init -> JaxTrainer.fit() -> one worker with a
fsdp=4 mesh. Nothing here loads a TPU library at import (the run is a
child process held to the CPU), and nothing it starts outlives it."""

import json
import os
import subprocess
import sys

from benchmark import common


def test_train_cell_rehearsal_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)          # run.py asks for the cell's four
    proc = subprocess.run(
        [sys.executable, os.path.join(common.HERE, "run.py"), "--workload",
         "mistral7b-train-fsdp4", "--seed", str(2**31 + 424242), "--seconds",
         "1", "--trace", "0", "--rehearsal"],
        env=env, cwd=common.ROOT, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    report, result = (json.loads(x) for x in
                      proc.stdout.strip().splitlines()[-2:])
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True and result["rehearsal"] is True
    assert result["device"]["platform"] == "cpu"     # never a device number
    assert result["device"]["count"] == 4
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    rep = report["report"]
    assert rep["extra"]["mesh"] == {"fsdp": 4}
    assert sum(rep["extra"]["collectives"].values()) > 0
    assert rep["checks"]["structure"]["ok"]
    assert not common.descendants()


def test_without_chips_the_command_exits_non_zero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(common.HERE, "run.py"), "--workload",
         "mistral7b-train-fsdp4", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, cwd=common.ROOT, capture_output=True,
        text=True, timeout=60)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
