"""End-to-end training example with fault injection.

Trains a reduced qwen3 on a synthetic token store, checkpoints through the
AirIndex manifest, injects a host failure mid-run, and shows the
supervisor restarting from the latest checkpoint with an elastically
shrunk host set.  Loss must decrease end to end.

Run:  PYTHONPATH=src python examples/train_llm.py [steps]
"""
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data.store import ShardedTokenStore, write_token_store
from repro.models import api
from repro.train.checkpoint import restore_checkpoint, save_checkpoint
from repro.train.fault_tolerance import (FTConfig, TrainingSupervisor,
                                         elastic_mesh_shape)
from repro.train.optimizer import adamw_init
from repro.train.train_step import TrainConfig, make_train_step
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

STEPS = int(sys.argv[1]) if len(sys.argv) > 1 else 40
workdir = tempfile.mkdtemp(prefix="airindex-train-")

cfg = get_config("qwen3-14b", smoke=True)
print(f"== training reduced {cfg.name}: {cfg.n_layers}L d{cfg.d_model} ==")

data_dir = os.path.join(workdir, "data")
rng = np.random.default_rng(0)
# learnable structure: repeated n-gram patterns
pats = [rng.integers(0, cfg.vocab, 16).astype(np.int32) for _ in range(8)]
samples = [np.concatenate([pats[i % 8]] * int(rng.integers(4, 16)))
           for i in range(512)]
write_token_store(data_dir, samples)
store = ShardedTokenStore(data_dir, profile="azure_ssd")
print(f"[data] index: {store.tune.design.describe()}")

tcfg = TrainConfig(microbatches=1)
params = api.init_params(cfg, jax.random.PRNGKey(0))
opt = adamw_init(params, tcfg.optimizer)
step_fn = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 1))
it = store.batch_iterator(4, 64, seed=0)
losses = []


def save(state, step):
    meta = save_checkpoint(workdir, state["params"], step=step,
                           profile="azure_ssd")
    print(f"[ckpt] step={step} blob={meta['blob_bytes']}B "
          f"manifest={meta['index_design']}")


def restore(step):
    # build the restore template from specs — the live params
    # were donated to step_fn and their buffers are gone
    like = jax.tree.map(lambda s: np.zeros(s.shape, s.dtype),
                        api.param_specs(cfg))
    tree, stats = restore_checkpoint(workdir, like, step=step)
    print(f"[restore] step={step} bytes={stats['bytes_read']} "
          f"reads={stats['reads']}")
    # fresh moments: the pre-failure opt state was donated to step_fn
    restored = jax.tree.map(jnp.asarray, tree)
    return {"params": restored, "opt": adamw_init(restored, tcfg.optimizer)}


sup = TrainingSupervisor(workdir, [f"host{i}" for i in range(4)],
                         FTConfig(checkpoint_every=10), save, restore)
killed = {"done": False}


def one_step(state, step):
    if step == 25 and not killed["done"]:
        print("[inject] killing host2 at step 25")
        sup.monitor.kill("host2")
        killed["done"] = True
    batch = next(it)
    p, o, m = step_fn(state["params"], state["opt"],
                      jax.tree.map(jnp.asarray, batch))
    losses.append(float(m["loss"]))
    if step % 5 == 0:
        print(f"[step {step:3d}] loss={losses[-1]:.4f}")
    return {"params": p, "opt": o}


t0 = time.time()
state, steps, log = sup.run({"params": params, "opt": opt}, one_step, STEPS)
events = [e["event"] for e in log]
new_mesh = elastic_mesh_shape(len(sup.monitor.hosts), 4, 2)
print(f"== done: {steps} steps in {time.time() - t0:.1f}s; "
      f"events={sorted(set(events))} ==")
print(f"surviving hosts={len(sup.monitor.hosts)} -> elastic mesh {new_mesh}")
print(f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")
assert "failure" in events and "restart" in events
assert losses[-1] < losses[0], "loss must decrease"
store.close()
print("OK")
