"""Native runtime tests: C++ TCPStore, blob queue, launcher (reference test
strategy SURVEY.md §4: all distributed plumbing exercisable on one host —
loopback store, local process pods)."""

import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

from paddle_tpu.distributed.store import TCPStore, load_native


def test_library_is_rebuilt_when_a_source_is_newer(tmp_path):
    """The library is not tracked: one left by another build of the tree
    must not outlive a change to ``native/*.cpp``."""
    from paddle_tpu.distributed.store import _needs_build

    src = tmp_path / "native" / "a.cpp"
    src.parent.mkdir()
    src.write_text("// source")
    lib = tmp_path / "lib.so"
    assert _needs_build(str(lib), str(src.parent))          # missing
    lib.write_bytes(b"")
    os.utime(src, (1_000, 1_000))
    os.utime(lib, (2_000, 2_000))
    assert not _needs_build(str(lib), str(src.parent))      # up to date
    os.utime(src, (3_000, 3_000))
    assert _needs_build(str(lib), str(src.parent))          # stale


class TestTCPStore:
    def test_set_get_roundtrip(self):
        s = TCPStore(is_master=True, world_size=1)
        s.set("k", b"value-bytes")
        assert s.get("k") == b"value-bytes"
        s.close()

    def test_add_counter(self):
        s = TCPStore(is_master=True, world_size=1)
        assert s.add("c", 5) == 5
        assert s.add("c", 7) == 12
        s.close()

    def test_get_blocks_until_set(self):
        s = TCPStore(is_master=True, world_size=1)
        got = []

        def waiter():
            c = TCPStore(port=s.port, world_size=1)
            got.append(c.get("late", timeout_ms=5000))
            c.close()

        t = threading.Thread(target=waiter)
        t.start()
        import time

        time.sleep(0.3)
        s.set("late", b"arrived")
        t.join(timeout=10)
        assert got == [b"arrived"]
        s.close()

    def test_wait_timeout(self):
        s = TCPStore(is_master=True, world_size=1)
        with pytest.raises(TimeoutError):
            s.wait("never", timeout_ms=200)
        s.close()

    def test_barrier_three_ranks(self):
        s = TCPStore(is_master=True, world_size=3)
        passed = []

        def rank(i):
            c = TCPStore(port=s.port, world_size=3)
            c.barrier("b", timeout_ms=5000)
            passed.append(i)
            c.close()

        ts = [threading.Thread(target=rank, args=(i,)) for i in (1, 2)]
        [t.start() for t in ts]
        s.barrier("b", timeout_ms=5000)
        [t.join(timeout=10) for t in ts]
        assert sorted(passed) == [1, 2]
        s.close()

    def test_delete_and_num_keys(self):
        s = TCPStore(is_master=True, world_size=1)
        s.set("a", b"1")
        s.set("b", b"2")
        assert s.num_keys() == 2
        assert s.delete_key("a")
        assert s.num_keys() == 1
        s.close()

    def test_large_value(self):
        s = TCPStore(is_master=True, world_size=1)
        blob = os.urandom(1 << 20)  # 1 MiB > initial 64 KiB client buffer
        s.set("big", blob)
        assert s.get("big") == blob
        s.close()


class TestBlobQueue:
    def test_push_pop_fifo(self):
        import ctypes

        lib = load_native()
        q = lib.dl_queue_create(4)
        for i in range(3):
            data = f"batch{i}".encode()
            assert lib.dl_queue_push(q, data, len(data), 1000) == 0
        assert lib.dl_queue_size(q) == 3
        for i in range(3):
            buf = ctypes.create_string_buffer(64)
            n = lib.dl_queue_pop(q, buf, 64, 1000)
            assert buf.raw[:n] == f"batch{i}".encode()
        lib.dl_queue_close(q)
        lib.dl_queue_destroy(q)

    def test_pop_timeout(self):
        lib = load_native()
        import ctypes

        q = lib.dl_queue_create(2)
        buf = ctypes.create_string_buffer(8)
        assert lib.dl_queue_pop(q, buf, 8, 100) == -1  # timeout
        lib.dl_queue_close(q)
        assert lib.dl_queue_pop(q, buf, 8, 100) == -2  # closed+drained
        lib.dl_queue_destroy(q)

    def test_bounded_capacity_blocks_producer(self):
        lib = load_native()
        q = lib.dl_queue_create(1)
        assert lib.dl_queue_push(q, b"x", 1, 100) == 0
        assert lib.dl_queue_push(q, b"y", 1, 100) == -1  # full → timeout
        lib.dl_queue_close(q)
        lib.dl_queue_destroy(q)


class TestLauncher:
    def test_single_proc_launch_env_contract(self, tmp_path):
        script = tmp_path / "worker.py"
        script.write_text(textwrap.dedent("""
            import os
            print("RANK", os.environ["PADDLE_TRAINER_ID"],
                  "WORLD", os.environ["PADDLE_TRAINERS_NUM"],
                  "EP", os.environ["PADDLE_CURRENT_ENDPOINT"])
        """))
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        rc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--log_dir", str(tmp_path / "log"), str(script)],
            cwd="/root/repo", env=env, timeout=60)
        assert rc.returncode == 0
        log = (tmp_path / "log" / "workerlog.0").read_text()
        assert "RANK 0 WORLD 1" in log

    def test_elastic_restart_on_failure(self, tmp_path):
        marker = tmp_path / "tries"
        script = tmp_path / "flaky.py"
        script.write_text(textwrap.dedent(f"""
            import os, sys
            p = {str(marker)!r}
            n = int(open(p).read()) if os.path.exists(p) else 0
            open(p, "w").write(str(n + 1))
            sys.exit(1 if n == 0 else 0)  # fail first run, succeed second
        """))
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        rc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--elastic_level", "1", "--max_restart", "2",
             "--log_dir", str(tmp_path / "log"), str(script)],
            cwd="/root/repo", env=env, timeout=60)
        assert rc.returncode == 0
        assert marker.read_text() == "2"

    def test_two_process_rendezvous_through_store(self, tmp_path):
        """A REAL 2-process pod: the launcher spawns both ranks, each
        connects to the master's C++ TCPStore from the env contract,
        crosses a barrier, publishes its rank key, and rank 0 verifies
        both arrived — the reference's loopback fake-multi-node recipe
        (SURVEY §4) end to end."""
        script = tmp_path / "worker.py"
        script.write_text(textwrap.dedent("""
            import os
            from paddle_tpu.distributed.store import TCPStore

            rank = int(os.environ["PADDLE_TRAINER_ID"])
            world = int(os.environ["PADDLE_TRAINERS_NUM"])
            master = os.environ["PADDLE_MASTER"]
            host, port = master.rsplit(":", 1)
            store = TCPStore(host=host, port=int(port),
                             is_master=(rank == 0), world_size=world)
            store.set(f"hello_{rank}", str(rank).encode())
            store.barrier("rdv", timeout_ms=30000)
            if rank == 0:
                got = sorted(int(store.get(f"hello_{r}", timeout_ms=10000))
                             for r in range(world))
                assert got == list(range(world)), got
                # the master must shut down LAST: wait for every other
                # rank's done-mark before closing the store server
                for r in range(1, world):
                    store.get(f"done_{r}", timeout_ms=10000)
                print("RENDEZVOUS-OK", got)
            else:
                store.set(f"done_{rank}", b"1")
            store.close()
        """))
        import socket

        with socket.socket() as s:  # unique master port: no cross-test
            s.bind(("127.0.0.1", 0))  # TIME_WAIT collisions on the default
            free_port = s.getsockname()[1]
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        rc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2",
             "--master", f"127.0.0.1:{free_port}",
             "--log_dir", str(tmp_path / "log"), str(script)],
            cwd="/root/repo", env=env, timeout=120)
        assert rc.returncode == 0
        log = (tmp_path / "log" / "workerlog.0").read_text()
        assert "RENDEZVOUS-OK [0, 1]" in log


_SPMD_WORKER = """
import os
import numpy as np
import jax
import paddle_tpu as paddle
import paddle_tpu.distributed as dist

env = dist.init_parallel_env()   # -> jax.distributed.initialize
rank = env.rank
assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 8, jax.device_count()
assert jax.local_device_count() == 4

# --- eager cross-process collectives (multi-controller runtime) ---
t = paddle.to_tensor(np.full((4,), float(rank + 1), np.float32))
dist.all_reduce(t)
np.testing.assert_allclose(t.numpy(), 3.0)

lst = []
dist.all_gather(lst, paddle.to_tensor(np.full((2,), float(rank), np.float32)))
assert len(lst) == 2, len(lst)
np.testing.assert_allclose(lst[0].numpy(), 0.0)
np.testing.assert_allclose(lst[1].numpy(), 1.0)

b = paddle.to_tensor(np.full((3,), float(rank * 7 + 1), np.float32))
dist.broadcast(b, src=1)
np.testing.assert_allclose(b.numpy(), 8.0)

objs = []
dist.all_gather_object(objs, {"rank": rank, "tag": "x" * (rank + 1)})
assert objs == [{"rank": 0, "tag": "x"}, {"rank": 1, "tag": "xx"}], objs

# all_gather must NOT overwrite its input buffer
src_buf = paddle.to_tensor(np.full((2,), float(rank), np.float32))
dist.all_gather([], src_buf)
assert tuple(src_buf.shape) == (2,), src_buf.shape

# scatter: reference convention — only src passes tensor_list
out_buf = paddle.to_tensor(np.zeros((2,), np.float32))
if rank == 0:
    got = dist.scatter(out_buf, tensor_list=[
        paddle.to_tensor(np.array([1., 2.], np.float32)),
        paddle.to_tensor(np.array([3., 4.], np.float32))], src=0)
else:
    got = dist.scatter(out_buf, src=0)
np.testing.assert_allclose(got.numpy(), [1., 2.] if rank == 0 else [3., 4.])

# reduce_scatter honors the reduce op
rs_in = paddle.to_tensor(np.arange(1, 5, dtype=np.float32) + rank)
got = dist.reduce_scatter(rs_in, op=dist.ReduceOp.MAX)
np.testing.assert_allclose(got.numpy(), [2., 3.] if rank == 0 else [4., 5.])

# DataParallel bucketed grad sync across the two processes: each rank
# backwards its batch shard; the synced grad must equal the full-batch
# gradient (reference Reducer semantics)
paddle.seed(5)
net = paddle.nn.Linear(8, 8)
dpm = paddle.DataParallel(net)
xfull = np.random.RandomState(7).randn(4, 8).astype(np.float32)
shard = paddle.to_tensor(xfull[rank * 2:(rank + 1) * 2])
paddle.mean(dpm(shard) ** 2).backward()
paddle.seed(5)
ref = paddle.nn.Linear(8, 8)
paddle.mean(ref(paddle.to_tensor(xfull)) ** 2).backward()
np.testing.assert_allclose(net.weight.grad.numpy(),
                           ref.weight.grad.numpy(), rtol=1e-5, atol=1e-6)

# --- one sharded llama train step over the global 2-process mesh ---
from jax.sharding import PartitionSpec as P

from paddle_tpu.models import llama
from paddle_tpu.parallel import create_hybrid_mesh, host_to_global

mesh = create_hybrid_mesh(dp=2, mp=4)  # dp axis spans the two processes
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
    print("SPMD-LLAMA-LOSS", repr(loss))
print("SPMD-WORKER-OK", rank)
"""


class TestMultiProcessSPMD:
    def test_launch_two_process_collectives_and_train_step(self, tmp_path):
        """The launcher->runtime->collective chain end to end (VERDICT r1
        item 3): the launcher spawns 2 workers; each joins the
        jax.distributed coordinator via init_parallel_env (4 virtual CPU
        devices per process -> 8 global), runs eager cross-process
        all_reduce/all_gather/broadcast/all_gather_object, then ONE sharded
        llama train step over a global dp=2 x mp=4 mesh. Rank 0's loss must
        match the same step computed single-process on this pytest
        process's own 8 local devices."""
        script = tmp_path / "spmd_worker.py"
        script.write_text(_SPMD_WORKER)
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            free_port = s.getsockname()[1]
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        rc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2",
             "--master", f"127.0.0.1:{free_port}",
             "--log_dir", str(tmp_path / "log"), str(script)],
            cwd="/root/repo", env=env, timeout=600,
            capture_output=True, text=True)
        log0 = (tmp_path / "log" / "workerlog.0")
        log1 = (tmp_path / "log" / "workerlog.1")
        detail = "\n".join(
            p.read_text()[-3000:] for p in (log0, log1) if p.exists())
        assert rc.returncode == 0, f"launch failed:\n{detail}"
        text0 = log0.read_text()
        assert "SPMD-WORKER-OK 0" in text0, text0[-3000:]
        assert "SPMD-WORKER-OK 1" in log1.read_text()

        # single-process reference on this process's 8 local devices
        import re

        m = re.search(r"SPMD-LLAMA-LOSS (\S+)", text0)
        assert m, text0[-3000:]
        loss_mp = float(m.group(1))

        from spmd_util import single_process_llama_loss

        loss_sp = single_process_llama_loss(dp=2, mp=4)
        np.testing.assert_allclose(loss_mp, loss_sp, rtol=2e-5)


def test_native_tsan_stress():
    """ThreadSanitizer lane for the C++ runtime (SURVEY.md §5.2 race
    detection; VERDICT r2 partial row): builds the store + prefetch queue
    with -fsanitize=thread and hammers them from 12 threads. Any data
    race makes TSAN print a report and exit non-zero."""
    import shutil

    if shutil.which("g++") is None:
        pytest.skip("no g++ in this environment")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(["make", "-C", "native", "tsan"], cwd=root,
                          capture_output=True, text=True, timeout=300)
    out = proc.stdout + proc.stderr
    if proc.returncode != 0 and ("libtsan" in out or "cannot find -ltsan"
                                 in out or "fsanitize=thread" in out
                                 and "unrecognized" in out):
        pytest.skip("toolchain lacks ThreadSanitizer support")
    assert proc.returncode == 0, out[-2000:]
    assert "ThreadSanitizer" not in out, out[-2000:]
    assert "tsan_stress OK" in out
