"""ElasticManager tests: heartbeat membership, dead-node detection,
scale-out (reference: elastic manager unit tests; SURVEY.md §5.3 —
tests kill workers to exercise restart)."""

import os
import time

from paddle_tpu.distributed.fleet.elastic import (ElasticManager,
                                                  ElasticStatus)


def test_membership_and_scale_events():
    m0 = ElasticManager("node0", is_master=True, ttl=1.0,
                        heartbeat_interval=0.2)
    m0.start()
    m1 = ElasticManager("node1", port=m0.store.port, ttl=1.0,
                        heartbeat_interval=0.2)
    m1.start()
    time.sleep(0.3)

    ev = m0.watch()  # first observation
    assert ev.status == ElasticStatus.NORMAL
    assert ev.alive == ["node0", "node1"]

    # scale-out: node2 joins
    m2 = ElasticManager("node2", port=m0.store.port, ttl=1.0,
                        heartbeat_interval=0.2)
    m2.start()
    time.sleep(0.3)
    ev = m0.watch()
    assert ev.status == ElasticStatus.SCALE_OUT and ev.joined == ["node2"]

    # scale-in: node1 dies (heartbeat stops, TTL expires)
    m1.stop()
    time.sleep(1.5)
    ev = m0.watch()
    assert ev.status == ElasticStatus.SCALE_IN and "node1" in ev.dead
    assert "node0" in ev.alive and "node2" in ev.alive

    # graceful leave drops the roster entry immediately
    m2.leave()
    time.sleep(1.5)
    ev = m0.watch()
    assert ev.status == ElasticStatus.SCALE_IN and ev.dead == ["node2"]

    m0.stop()
    m0.store.close()


_ELASTIC_TRAIN_WORKER = """
import os
import sys
import numpy as np
import paddle_tpu as paddle

rank = int(os.environ["PADDLE_TRAINER_ID"])
ckpt = os.environ["CKPT_PATH"]
marker = os.environ["KILL_MARKER"]
TOTAL = 6

paddle.seed(3)
model = paddle.nn.Linear(8, 8)
opt = paddle.optimizer.SGD(learning_rate=0.05,
                           parameters=model.parameters())
start = 0
if os.path.exists(ckpt + ".pdparams"):
    state = paddle.load(ckpt + ".pdparams")
    start = int(state.pop("__step__"))
    model.set_state_dict(state)
    print(f"RESUMED-FROM {start}", flush=True)

rng = np.random.RandomState(11)
xs = [rng.randn(4, 8).astype("float32") for _ in range(TOTAL)]
import time
for step in range(start, TOTAL):
    loss = paddle.mean(model(paddle.to_tensor(xs[step])) ** 2)
    loss.backward()
    opt.step()
    opt.clear_grad()
    if rank == 0:
        state = model.state_dict()
        state["__step__"] = step + 1
        paddle.save(state, ckpt + ".pdparams")
    time.sleep(0.15)  # pace steps so the ranks' incarnations overlap
    if rank == 1 and step >= 2 and not os.path.exists(marker):
        # kill only once a checkpoint exists, so the restart provably
        # RESUMES (not restarts from scratch) even on a loaded machine
        if os.path.exists(ckpt + ".pdparams"):
            open(marker, "w").write("killed")
            import signal
            os.kill(os.getpid(), signal.SIGKILL)  # die mid-training, hard
print(f"FINAL-STEP {TOTAL} rank {rank}", flush=True)
"""


class TestElasticEndToEnd:
    def test_kill_worker_restart_resumes_from_checkpoint(self, tmp_path):
        """SURVEY §5.3 end to end: a 2-worker pod under --elastic_level 1;
        rank 1 SIGKILLs itself mid-step on the first incarnation; the
        launcher must restart the pod and training must RESUME from the
        checkpoint (not restart from scratch)."""
        import subprocess
        import sys as _sys
        import textwrap

        script = tmp_path / "train.py"
        script.write_text(_ELASTIC_TRAIN_WORKER)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["CKPT_PATH"] = str(tmp_path / "ckpt")
        env["KILL_MARKER"] = str(tmp_path / "killed")
        rc = subprocess.run(
            [_sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2", "--elastic_level", "1",
             "--max_restart", "2",
             "--log_dir", str(tmp_path / "log"), str(script)],
            cwd="/root/repo", env=env, timeout=300,
            capture_output=True, text=True)
        log0 = (tmp_path / "log" / "workerlog.0").read_text()
        log1 = (tmp_path / "log" / "workerlog.1").read_text()
        assert rc.returncode == 0, (rc.stderr[-2000:], log0[-1500:])
        assert (tmp_path / "killed").exists()
        assert "elastic restart 1/2" in rc.stderr
        # second incarnation resumed from a mid-training checkpoint
        import re

        resumes = [int(m) for m in re.findall(r"RESUMED-FROM (\d+)", log0)]
        assert resumes and resumes[-1] >= 1, log0[-1500:]
        assert "FINAL-STEP 6 rank 0" in log0
        assert "FINAL-STEP 6 rank 1" in log1


class TestElasticMonitorWiring:
    def test_pod_watch_reports_membership_change(self, tmp_path):
        """The launcher's elastic hook: a monitor returning True makes
        pod.watch return MEMBERSHIP_CHANGED so the controller restarts."""
        import sys as _sys

        from paddle_tpu.distributed.launch.main import Container, Pod

        pod = Pod()
        pod.add(Container([_sys.executable, "-c", "import time; time.sleep(30)"],
                          {}, str(tmp_path / "w.log")))
        pod.start()
        hits = []

        def monitor():
            hits.append(1)
            return len(hits) >= 2

        rc = pod.watch(monitor=monitor)
        pod.stop()
        assert rc == Pod.MEMBERSHIP_CHANGED
        assert len(hits) == 2
