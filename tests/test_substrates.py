"""Substrate tests: data store, checkpoint manifest, fault tolerance,
gradient compression, KV-cache page tables, sharding rules."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.data.store import ShardedTokenStore, write_token_store
from repro.serve.kvcache import PagedKVCache
from repro.train.checkpoint import restore_checkpoint, save_checkpoint
from repro.train.compression import compress_decompress, compressed_psum
from repro.train.fault_tolerance import (FTConfig, TrainingSupervisor,
                                         elastic_mesh_shape, rescale_batch)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------
def test_sosd_like_is_reproducible_across_processes():
    """The generators seed from (name, seed) alone: a salted str hash()
    would change these keys from one interpreter to the next."""
    import zlib

    from repro.data.datasets import sosd_like
    keys = sosd_like("wiki", 10_000, 0)
    assert (len(keys), int(keys[0]), int(keys[-1])) == (9226, 3, 61690)
    assert zlib.crc32(keys.tobytes()) == 3686618619


@pytest.fixture(scope="module")
def token_store(tmp_path_factory):
    rng = np.random.default_rng(0)
    samples = [rng.integers(0, 1000, rng.integers(20, 300)).astype(np.int32)
               for _ in range(500)]
    path = str(tmp_path_factory.mktemp("store"))
    write_token_store(path, samples)
    store = ShardedTokenStore(path, profile="azure_ssd")
    yield store, samples
    store.close()


def test_store_random_access_exact(token_store):
    store, samples = token_store
    rng = np.random.default_rng(1)
    for i in rng.integers(0, len(samples), 50):
        got = store.get(int(i))
        np.testing.assert_array_equal(got, samples[int(i)])


def test_store_partial_reads(token_store):
    store, samples = token_store
    before = store.index.bytes_read
    for i in range(30):
        store.get(i)
    # reads should be range-sized, not whole-file-sized
    total = sum(len(s) * 4 for s in samples)
    assert store.index.bytes_read - before < total


def test_store_batch_iterator_replayable(token_store):
    store, _ = token_store
    a = [next(store.batch_iterator(4, 64, seed=7, start_step=i))
         for i in range(3)]
    b = list(__import__("itertools").islice(
        store.batch_iterator(4, 64, seed=7), 3))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x["tokens"], y["tokens"])


# ---------------------------------------------------------------------------
# checkpoint with AirIndex manifest
# ---------------------------------------------------------------------------
def test_checkpoint_roundtrip(tmp_path):
    rng = np.random.default_rng(0)
    tree = {"a": rng.normal(size=(100, 64)).astype(np.float32),
            "b": {"w": rng.normal(size=(257,)).astype(np.float32),
                  "s": np.int32(7)}}
    save_checkpoint(str(tmp_path), tree, profile="azure_ssd", step=3)
    like = jax.tree.map(lambda x: np.zeros_like(x), tree)
    out, stats = restore_checkpoint(str(tmp_path), like, step=3)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a).reshape(-1),
                                      np.asarray(b).reshape(-1))
    assert stats["slices_read"] >= 3


def test_checkpoint_partial_restore(tmp_path):
    rng = np.random.default_rng(0)
    tree = {"big": rng.normal(size=(3 << 20,)).astype(np.float32),  # 12 MB
            "small": rng.normal(size=(64,)).astype(np.float32)}
    save_checkpoint(str(tmp_path), tree, profile="azure_ssd", step=0)
    like = jax.tree.map(np.zeros_like, tree)
    out, stats = restore_checkpoint(str(tmp_path), like, step=0,
                                    leaf_filter=lambda n: n == "small")
    assert out["big"] is None
    np.testing.assert_array_equal(out["small"], tree["small"])
    # partial restore reads ≪ blob size
    assert stats["bytes_read"] < 2 << 20


def test_checkpoint_detects_corruption(tmp_path):
    tree = {"w": np.arange(4096, dtype=np.float32)}
    save_checkpoint(str(tmp_path), tree, profile="azure_ssd", step=0)
    blob = os.path.join(str(tmp_path), "ckpt-0.blob")
    with open(blob, "r+b") as f:
        f.seek(100)
        f.write(b"\xff\xff")
    with pytest.raises(AssertionError, match="corrupt"):
        restore_checkpoint(str(tmp_path), jax.tree.map(np.zeros_like, tree),
                           step=0)


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------
def test_supervisor_restarts_from_checkpoint(tmp_path):
    saved = {}

    def save_fn(state, step):
        saved[step] = dict(state)
        open(os.path.join(str(tmp_path), f"ckpt-{step}.json"), "w").write("{}")

    def restore_fn(step):
        return dict(saved[step])

    sup = TrainingSupervisor(str(tmp_path), ["h0", "h1", "h2", "h3"],
                             FTConfig(checkpoint_every=5), save_fn, restore_fn)
    state = {"x": 0}
    killed = {"done": False}

    def step_fn(st, step):
        if step == 12 and not killed["done"]:
            sup.monitor.kill("h2")       # inject a failure mid-run
            killed["done"] = True
        return {"x": st["x"] + 1}

    state, steps, log = sup.run(state, step_fn, n_steps=20)
    events = [e["event"] for e in log]
    assert "failure" in events and "restart" in events
    assert steps == 20
    assert len(sup.monitor.hosts) == 3         # h2 removed
    # the run replayed steps 10–12 after restoring from the step-10 ckpt
    assert state["x"] >= 20 - 10


def test_elastic_mesh_and_batch_rescale():
    assert elastic_mesh_shape(16, 16, 16) == (16, 16)
    assert elastic_mesh_shape(15, 16, 16) == (8, 16)   # power-of-two shrink
    assert rescale_batch(256, 16, 8) == 32
    with pytest.raises(AssertionError):
        rescale_batch(250, 16, 16)


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------
def test_int8_quantization_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1000,)), jnp.float32)
    y = compress_decompress(x)
    scale = float(jnp.max(jnp.abs(x)))
    assert float(jnp.max(jnp.abs(x - y))) <= scale / 127.0 + 1e-6


def test_compressed_psum_error_feedback():
    """Error feedback: mean of compressed reductions over repeated steps
    converges to the true mean (the residual is carried, not lost)."""
    from jax.sharding import Mesh, PartitionSpec as P
    from jax.experimental.shard_map import shard_map
    devs = np.array(jax.devices()[:1]).reshape(1)
    mesh = Mesh(devs, ("pod",))
    rng = np.random.default_rng(0)
    g_true = jnp.asarray(rng.normal(size=(64,)), jnp.float32)

    @jax.jit
    def step(err):
        f = shard_map(lambda e: compressed_psum({"g": g_true}, {"g": e},
                                                "pod"),
                      mesh=mesh, in_specs=P(), out_specs=P(),
                      check_rep=False)
        return f(err)

    err = jnp.zeros((64,), jnp.float32)
    acc = jnp.zeros_like(g_true)
    n = 30
    for _ in range(n):
        mean, errs = step(err)
        err = errs["g"]
        acc = acc + mean["g"]
    # accumulated compressed means ≈ n · true grad (error feedback works)
    rel = float(jnp.linalg.norm(acc / n - g_true) / jnp.linalg.norm(g_true))
    assert rel < 0.02, rel


# ---------------------------------------------------------------------------
# paged KV cache + tuned page table
# ---------------------------------------------------------------------------
def test_paged_kvcache_pool():
    pool = PagedKVCache(n_pages=8, page_tokens=16)
    pool.add_sequence(0)
    pool.append_tokens(0, 40)         # 3 pages
    assert len(pool.tables[0]) == 3
    pool.add_sequence(1)
    pool.append_tokens(1, 80)         # 5 pages
    with pytest.raises(MemoryError):
        pool.append_tokens(1, 16)     # pool exhausted
    pool.release(0)
    pool.append_tokens(1, 16)         # freed pages reused
    assert len(pool.free) == 2


def test_page_table_tuning_beats_flat():
    rng = np.random.default_rng(0)
    pool = PagedKVCache(n_pages=65536)
    for s in range(128):
        pool.add_sequence(s)
        pool.append_tokens(s, int(rng.integers(256, 4096)))
    stats = pool.modeled_lookup_cost("host_dram")
    assert stats["tuned_us"] <= stats["flat_us"] * 1.0001


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------
def test_param_shardings_cover_all_archs():
    from jax.sharding import Mesh
    from repro.configs import ARCHS, get_config
    from repro.dist.sharding import param_shardings
    from repro.models import api
    devs = np.array(jax.devices() * 1)[:1].reshape(1, 1)
    mesh = Mesh(devs, ("data", "model"))
    for arch in ARCHS:
        cfg = get_config(arch)
        specs = api.param_specs(cfg)
        sh = param_shardings(cfg, specs, mesh)
        # every leaf got a sharding and every spec is valid for its shape
        for s, spec in zip(jax.tree.leaves(specs), jax.tree.leaves(sh)):
            assert spec is not None
