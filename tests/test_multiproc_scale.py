"""Multi-process runtime at >= 4 ranks (VERDICT r4 item 4).

The virtual 8-device mesh proves SPMD semantics; these tests exercise the
MULTI-PROCESS runtime path — launcher pods, jax.distributed bootstrap,
eager cross-process collectives (ring order beyond a 2-cycle), bucketed
DataParallel, the sharded parameter-server fleet, elastic membership at
4 nodes, and C++ TCPStore contention — at world sizes the reference's CI
runs (SURVEY §4 distributed-tests row: launcher-driven N-proc parity)."""

import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


_SPMD4_WORKER = """
import os
import numpy as np
import jax
import paddle_tpu as paddle
import paddle_tpu.distributed as dist

env = dist.init_parallel_env()
rank = env.rank
W = 4
assert jax.process_count() == W, jax.process_count()
assert jax.device_count() == 8, jax.device_count()
assert jax.local_device_count() == 2

# ring order is a real 4-cycle here, not the degenerate 2-swap
t = paddle.to_tensor(np.full((4,), float(rank + 1), np.float32))
dist.all_reduce(t)
np.testing.assert_allclose(t.numpy(), 10.0)  # 1+2+3+4

lst = []
dist.all_gather(lst, paddle.to_tensor(np.full((2,), float(rank),
                                              np.float32)))
assert len(lst) == W, len(lst)
for r in range(W):
    np.testing.assert_allclose(lst[r].numpy(), float(r))

b = paddle.to_tensor(np.full((3,), float(rank * 7 + 1), np.float32))
dist.broadcast(b, src=2)
np.testing.assert_allclose(b.numpy(), 15.0)

# reduce_scatter: 8 elements -> 2 per rank; MAX over ranks = value + 3
rs_in = paddle.to_tensor(np.arange(1, 9, dtype=np.float32) + rank)
got = dist.reduce_scatter(rs_in, op=dist.ReduceOp.MAX)
np.testing.assert_allclose(got.numpy(),
                           np.arange(1, 9, dtype=np.float32)[
                               2 * rank:2 * rank + 2] + 3)

# alltoall_single: row j of rank r is r*4+j; after exchange rank r holds
# row r of every rank = [r, 4+r, 8+r, 12+r]
a2a = paddle.to_tensor(
    (np.arange(4, dtype=np.float32) + 4.0 * rank)[:, None].repeat(2, 1))
out = dist.alltoall_single(a2a, None)
want = (np.arange(4, dtype=np.float32) * 4 + rank)[:, None].repeat(2, 1)
np.testing.assert_allclose(np.asarray(
    getattr(out, "numpy", lambda: out)()), want)

objs = []
dist.all_gather_object(objs, {"rank": rank})
assert [o["rank"] for o in objs] == list(range(W)), objs

# DataParallel bucketed grad sync over FOUR processes: each rank
# backwards a 2-row shard; synced grad == full-batch gradient
paddle.seed(5)
net = paddle.nn.Linear(8, 8)
dpm = paddle.DataParallel(net)
xfull = np.random.RandomState(7).randn(8, 8).astype(np.float32)
shard = paddle.to_tensor(xfull[rank * 2:(rank + 1) * 2])
paddle.mean(dpm(shard) ** 2).backward()
paddle.seed(5)
ref = paddle.nn.Linear(8, 8)
paddle.mean(ref(paddle.to_tensor(xfull)) ** 2).backward()
np.testing.assert_allclose(net.weight.grad.numpy(),
                           ref.weight.grad.numpy(), rtol=1e-5, atol=1e-6)

# one sharded llama train step over the global dp=4 x mp=2 mesh
from jax.sharding import PartitionSpec as P
from paddle_tpu.models import llama
from paddle_tpu.parallel import create_hybrid_mesh, host_to_global

mesh = create_hybrid_mesh(dp=4, mp=2)
cfg = llama.LlamaConfig.tiny()
params = llama.init_params(cfg)
opt = llama.init_opt_state(params)
ps = llama.param_specs(cfg)
os_ = llama.opt_state_specs(cfg)
gparams = {k: host_to_global(np.asarray(v), ps[k], mesh)
           for k, v in params.items()}
gopt = {
    "step": host_to_global(np.asarray(opt["step"]), P(), mesh),
    "m": {k: host_to_global(np.asarray(v), os_[k], mesh)
          for k, v in opt["m"].items()},
    "v": {k: host_to_global(np.asarray(v), os_[k], mesh)
          for k, v in opt["v"].items()},
}
tokens = np.random.RandomState(0).randint(
    0, cfg.vocab_size, (4, 64)).astype(np.int32)
gtok = host_to_global(tokens, P(("dp", "sharding"), None), mesh)
step = llama.make_sharded_train_step(cfg, mesh, lr=1e-3)
_, _, loss = step(gparams, gopt, gtok, gtok)
loss = float(np.asarray(loss.addressable_data(0)))
if rank == 0:
    print("SPMD4-LLAMA-LOSS", repr(loss))
print("SPMD4-WORKER-OK", rank)
"""


class TestFourProcessSPMD:
    @pytest.mark.slow
    def test_launch_four_process_collectives_and_dp_parity(self, tmp_path):
        """Launcher-driven FOUR-process pod (2 virtual devices each -> 8
        global): eager collectives whose ring is a true 4-cycle, 4-rank
        bucketed DataParallel parity vs the full batch, and one sharded
        train step on a dp=4 x mp=2 mesh matching the single-process
        loss.

        slow-marked (r21 suite-time claw-back): the 2-process launcher
        path stays tier-1 via test_native_launch.py's
        test_launch_two_process_collectives_and_train_step; this run
        only scales the same code path to 4 subprocesses."""
        script = tmp_path / "spmd4_worker.py"
        script.write_text(_SPMD4_WORKER)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        rc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "4",
             "--master", f"127.0.0.1:{_free_port()}",
             "--log_dir", str(tmp_path / "log"), str(script)],
            cwd="/root/repo", env=env, timeout=600,
            capture_output=True, text=True)
        logs = [tmp_path / "log" / f"workerlog.{r}" for r in range(4)]
        detail = "\n".join(p.read_text()[-2000:] for p in logs
                           if p.exists())
        assert rc.returncode == 0, f"launch failed:\n{detail}"
        text0 = logs[0].read_text()
        for r in range(4):
            assert f"SPMD4-WORKER-OK {r}" in logs[r].read_text()

        # single-process reference on this pytest process's 8 devices
        import re

        m = re.search(r"SPMD4-LLAMA-LOSS (\S+)", text0)
        assert m, text0[-3000:]
        loss_mp = float(m.group(1))

        from spmd_util import single_process_llama_loss

        loss_sp = single_process_llama_loss(dp=4, mp=2)
        np.testing.assert_allclose(loss_mp, loss_sp, rtol=2e-5)


_PS_2S4T_WORKER = """
import os
import time
import numpy as np

role = os.environ["TRAINING_ROLE"]
eps = os.environ["PADDLE_PSERVERS_IP_PORT_LIST"].split(",")

if role == "PSERVER":
    from paddle_tpu.distributed.ps import PsServer

    port = int(os.environ["PADDLE_PORT"])
    s = PsServer(port=port)
    print("PSERVER-UP", port, flush=True)
    while True:
        time.sleep(0.5)

from paddle_tpu.distributed.ps import ShardedPsClient

rank = int(os.environ["PADDLE_TRAINER_ID"])
world = int(os.environ["PADDLE_TRAINERS_NUM"])
assert len(eps) == 2, eps
assert world == 4, world
c = ShardedPsClient(",".join(eps))
if rank == 0:
    c.create_dense_table(0, (4, 2), lr=0.05,
                         init=np.zeros((4, 2), np.float32))
    c.create_sparse_table(1, dim=2, lr=0.1)
c.barrier("init", world)

# 4 trainers jointly fit a row-partitioned dense table spanning BOTH
# servers; each also touches its own sparse row (hash fan-out)
rng = np.random.RandomState(100 + rank)
target = np.array([[3.0, -1.0], [0.5, 2.0], [-2.0, 1.0], [1.0, 1.0]],
                  np.float32)
for step in range(80):
    w = c.pull_dense(0)
    x = rng.randn(8, 4).astype(np.float32)
    y = x @ target
    grad = 2 * x.T @ (x @ w - y) / len(x)
    c.push_dense_grad(0, grad)
    c.push_sparse_grad(1, [rank], np.ones((1, 2), np.float32) * 0.01)
c.barrier("done", world)
if rank == 0:
    w = c.pull_dense(0)
    err = float(np.abs(w - target).max())
    stats = c.table_stats()
    assert err < 0.2, (w, err)
    assert stats["sparse"][1] == world, stats
    print("PS-2S4T-OK err", round(err, 4), flush=True)
c.close()
"""


@pytest.mark.slow
def test_launcher_ps_two_servers_four_trainers(tmp_path):
    """--run_mode ps at fleet scale: 2 servers x 4 trainers; the dense
    table row-partitions across both servers, all four trainers push
    grads concurrently, sparse rows fan out one per trainer, and the
    launcher tears both servers down at the end.

    slow-marked (r21 suite-time claw-back): PS push/pull/partition
    logic is covered by test_ps.py and the launcher plumbing by the
    2-process tier-1 runs; this is the same path at 6 subprocesses."""
    script = tmp_path / "ps_worker.py"
    script.write_text(_PS_2S4T_WORKER)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    rc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--run_mode", "ps", "--server_num", "2", "--trainer_num", "4",
         "--log_dir", str(tmp_path / "log"), str(script)],
        cwd="/root/repo", env=env, timeout=300,
        capture_output=True, text=True)
    log0_path = tmp_path / "log" / "workerlog.0"
    log0 = log0_path.read_text() if log0_path.exists() else "(no log)"
    assert rc.returncode == 0, (rc.stderr[-1500:], log0[-1500:])
    for s in range(2):
        assert "PSERVER-UP" in (
            tmp_path / "log" / f"serverlog.{s}").read_text()
    assert "PS-2S4T-OK" in log0


def test_elastic_shrink_four_to_three():
    """Elastic membership at 4 nodes: one node dies (TTL expiry, no
    graceful leave); the master AND a surviving peer must both observe
    the shrink to exactly the 3 survivors."""
    from paddle_tpu.distributed.fleet.elastic import (ElasticManager,
                                                      ElasticStatus)

    m0 = ElasticManager("node0", is_master=True, ttl=1.0,
                        heartbeat_interval=0.2)
    m0.start()
    peers = [ElasticManager(f"node{i}", port=m0.store.port, ttl=1.0,
                            heartbeat_interval=0.2) for i in (1, 2, 3)]
    for p in peers:
        p.start()
    try:
        time.sleep(0.4)
        ev = m0.watch()
        assert ev.status == ElasticStatus.NORMAL
        assert ev.alive == [f"node{i}" for i in range(4)], ev.alive

        peers[1].stop()  # node2 dies hard: heartbeats stop, TTL expires
        time.sleep(1.6)
        ev = m0.watch()
        assert ev.status == ElasticStatus.SCALE_IN and "node2" in ev.dead
        assert sorted(ev.alive) == ["node0", "node1", "node3"], ev.alive
        # a SURVIVOR (not only the master) sees the same roster
        ev1 = peers[0].watch()
        assert sorted(ev1.alive) == ["node0", "node1", "node3"], ev1.alive
    finally:
        for p in (peers[0], peers[2]):
            p.stop()
        m0.stop()
        m0.store.close()


def test_tcpstore_contention_eight_clients():
    """C++ TCPStore under real 8-client contention: concurrent add() on a
    shared counter (atomicity), interleaved set/get of per-client keys
    (no cross-talk), and an 8-way barrier. Socket ops release the GIL, so
    the server sees genuinely concurrent connections."""
    from paddle_tpu.distributed.store import TCPStore

    W, OPS = 8, 50
    master = TCPStore(host="127.0.0.1", port=0, is_master=True,
                      world_size=W)
    errors = []

    def client(tid, store):
        try:
            for i in range(OPS):
                store.add("ctr", 1)
                store.set(f"k_{tid}_{i}", f"v{tid}:{i}".encode())
                got = store.get(f"k_{tid}_{i}", timeout_ms=10000)
                assert got == f"v{tid}:{i}".encode(), (tid, i, got)
            # cross-client read: wait for the NEXT client's first key
            nxt = (tid + 1) % W
            got = store.get(f"k_{nxt}_0", timeout_ms=10000)
            assert got == f"v{nxt}:0".encode()
            store.barrier("drain", timeout_ms=30000)
        except Exception as e:  # surface thread failures to pytest
            errors.append((tid, repr(e)))

    clients = [TCPStore(host="127.0.0.1", port=master.port,
                        is_master=False, world_size=W) for _ in range(7)]
    threads = [threading.Thread(target=client, args=(t + 1, s))
               for t, s in enumerate(clients)]
    for t in threads:
        t.start()
    client(0, master)  # the master process is participant 0
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    assert master.add("ctr", 0) == W * OPS  # atomic under contention
    for s in clients:
        s.close()
    master.close()
