"""Multi-device semantics (8 virtual CPU devices via subprocess — the main
test process must keep seeing 1 device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=420,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_repartition_primitives():
    """The plan-driven row movers: hash repartition preserves every live row
    exactly once, lands equal keys on the hash-owner shard (co-partitioning),
    and broadcast replicates the full row set on every shard."""
    out = _run(
        """
        import functools
        import numpy as np, jax, jax.numpy as jnp, collections
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.exec import distributed as D
        from repro.dicts import base as dbase
        from repro import compat
        mesh = compat.make_mesh((2,4), ("pod","data"))
        axis = ("pod","data")
        rng = np.random.default_rng(1)
        N = 8*256
        keys = rng.integers(0, 150, N).astype(np.int32)
        vals = rng.normal(size=N).astype(np.float32)
        mask = rng.random(N) < 0.8
        gk = jax.device_put(jnp.asarray(keys), NamedSharding(mesh, P(axis)))
        gv = jax.device_put(jnp.asarray(vals), NamedSharding(mesh, P(axis)))
        gm = jax.device_put(jnp.asarray(mask), NamedSharding(mesh, P(axis)))

        def body(k, m, v):
            nm, cols = D.repartition_cols(k, m, {"k": k, "v": v}, axis)
            owner = (dbase._mix(cols["k"], dbase._H2) % jnp.uint32(8)).astype(jnp.int32)
            ok = jnp.where(nm, owner == jax.lax.axis_index(axis), True)
            return nm, cols["k"], cols["v"], ok

        nm, nk, nv, ok = compat.shard_map(
            body, mesh=mesh, in_specs=(P(axis), P(axis), P(axis)),
            out_specs=(P(axis), P(axis), P(axis), P(axis)),
        )(gk, gm, gv)
        nm, nk, nv, ok = map(np.asarray, (nm, nk, nv, ok))
        assert ok.all()                      # every live row is on its owner
        assert nm.sum() == mask.sum()        # no row lost or duplicated
        got = sorted(zip(nk[nm].tolist(), nv[nm].tolist()))
        want = sorted(zip(keys[mask].tolist(), vals[mask].tolist()))
        assert got == want

        def bcast(k, m, v):
            nm, cols = D.broadcast_cols(m, {"k": k, "v": v}, axis)
            return nm, cols["k"], cols["v"]

        bm, bk, bv = compat.shard_map(
            bcast, mesh=mesh, in_specs=(P(axis), P(axis), P(axis)),
            out_specs=(P(axis), P(axis), P(axis)),
        )(gk, gm, gv)
        bm, bk, bv = map(np.asarray, (bm, bk, bv))
        # every shard's gathered slice holds the full live row set
        for s in range(8):
            sl = slice(s*N, (s+1)*N)
            got = sorted(zip(bk[sl][bm[sl]].tolist(), bv[sl][bm[sl]].tolist()))
            assert got == want
        print("REPART_OK")
        """
    )
    assert "REPART_OK" in out


def test_compressed_psum_and_lowcard():
    out = _run(
        """
        import numpy as np, jax, jax.numpy as jnp, functools
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.train.optimizer import compressed_psum
        from repro.exec import distributed as D
        from repro import compat
        mesh = compat.make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32))
        gs = jax.device_put(g, NamedSharding(mesh, P("data", None)))

        def body(gl, ef):
            out, new_ef = compressed_psum({"g": gl}, {"g": ef}, "data")
            return out["g"], new_ef["g"]
        summed, _ = compat.shard_map(
            body, mesh=mesh, in_specs=(P("data", None), P("data", None)),
            out_specs=(P("data", None), P("data", None)),
        )(gs, jnp.zeros_like(gs))
        want = np.asarray(g).sum(axis=0)
        got = np.asarray(summed)[0]
        err = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
        assert err < 0.05, err  # int8 quantization error bound

        keys = jax.device_put(jnp.asarray(rng.integers(0, 6, 8*16).astype(np.int32)),
                              NamedSharding(mesh, P("data")))
        vals = jax.device_put(jnp.asarray(rng.normal(size=(8*16, 1)).astype(np.float32)),
                              NamedSharding(mesh, P("data", None)))
        fn = functools.partial(D.dist_groupby_lowcard_shard, axis="data", n_groups=6)
        acc, cnt = compat.shard_map(fn, mesh=mesh, in_specs=(P("data"), P("data", None)),
                                 out_specs=(P(), P()))(keys, vals)
        import collections
        exp = collections.defaultdict(float)
        for k, v in zip(np.asarray(keys), np.asarray(vals)[:,0]): exp[int(k)] += float(v)
        for k in exp:
            np.testing.assert_allclose(np.asarray(acc)[k,0], exp[k], rtol=1e-3)
        print("PSUM_OK")
        """
    )
    assert "PSUM_OK" in out


def test_trainer_on_host_mesh_data_parallel():
    """End-to-end DP training on an 8-device mesh (auto-sharded jit)."""
    out = _run(
        """
        import numpy as np, jax
        from repro.models.registry import get_model_by_name
        from repro.data.lm_data import StreamConfig
        from repro.train.train_loop import Trainer, TrainConfig
        from repro.train.optimizer import OptConfig
        m = get_model_by_name("llama3.2-3b", reduced=True)
        scfg = StreamConfig(vocab=m.cfg.vocab, global_batch=8, seq_len=16, seed=0)
        tc = TrainConfig(steps=4, ckpt_every=100, ckpt_dir="/tmp/dp_ck",
                         ckpt_async=False, log_every=1000,
                         opt=OptConfig(lr=1e-3, warmup_steps=1, total_steps=4))
        t = Trainer(m, tc, scfg); t.init()
        log = t.run()
        assert all(np.isfinite(x["loss"]) for x in log)
        print("DP_TRAIN_OK", round(log[0]["loss"],3), "->", round(log[-1]["loss"],3))
        """
    )
    assert "DP_TRAIN_OK" in out


def test_ring_allgather_matmul_overlap():
    out = _run(
        """
        import numpy as np, jax, jax.numpy as jnp, functools
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.sharding.overlap import ring_allgather_matmul, allgather_matmul_reference
        from repro import compat
        mesh = compat.make_mesh((8,), ("tp",))
        rng = np.random.default_rng(0)
        X = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))
        W = jnp.asarray(rng.normal(size=(32, 16)).astype(np.float32))
        Xs = jax.device_put(X, NamedSharding(mesh, P("tp", None)))
        ring = compat.shard_map(functools.partial(ring_allgather_matmul, axis="tp"),
                             mesh=mesh, in_specs=(P("tp", None), P(None, None)),
                             out_specs=P(None, None))(Xs, W)
        ref = compat.shard_map(functools.partial(allgather_matmul_reference, axis="tp"),
                            mesh=mesh, in_specs=(P("tp", None), P(None, None)),
                            out_specs=P(None, None))(Xs, W)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(ref), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(X @ W), rtol=1e-4)
        print("RING_OK")
        """
    )
    assert "RING_OK" in out


def test_sharded_inputs_placed_on_mesh():
    """A sharded session's executor places its inputs once, at build: the
    fact relations split along the mesh axis (each of 4 devices holds a
    quarter of lineitem), dimensions replicated on every device."""
    out = _run(
        """
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.data import tpch
        from repro.session import connect

        mesh_devices = jax.devices()[:4]
        db = tpch.generate(scale=0.002, seed=3).tables()
        sess = connect(dict(db), shards=4)
        cols, masks = sess.shape("q3").executable.inputs
        for rel, rc in cols.items():
            want = P("data") if rel in sess.shard_rels else P()
            for a in list(rc.values()) + [masks[rel]]:
                assert isinstance(a.sharding, NamedSharding), (rel, a.sharding)
                assert a.sharding.spec == want, (rel, a.sharding.spec)
                assert set(a.sharding.device_set) == set(mesh_devices), rel
        li = cols["lineitem"]["orderkey"]
        per_dev = sorted(s.data.shape[0] for s in li.addressable_shards)
        assert per_dev == [li.shape[0] // 4] * 4, per_dev
        print("OK")
        """
    )
    assert "OK" in out
