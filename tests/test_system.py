"""End-to-end behaviour: the paper's pipeline (generate -> rank -> verify),
LM training convergence on the smoke config, serving round trips, and the
on-chip entry point's refusal to run without a TPU."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import num_components, random_splitter_rank, shiloach_vishkin
from repro.core.serial import canonicalize_labels, serial_connected_components, serial_list_rank
from repro.data.lm import lm_batch
from repro.ops.kiss import random_forest, random_linked_list
from repro.train.loop import LoopConfig, train
from repro.train.optimizer import AdamWConfig


def test_paper_pipeline_end_to_end():
    """KISS input generation -> random-splitter ranking (AoS packing,
    Pallas-backed phases) -> serial verification; then graph CC."""
    n = 50_000
    succ = random_linked_list(n, seed=1)
    rank = np.asarray(random_splitter_rank(succ, 512, seed=2, pack_mode="aos"))
    np.testing.assert_array_equal(rank, serial_list_rank(succ))

    edges = random_forest(5_000, num_components=25, seed=3)
    labels, rounds = shiloach_vishkin(edges[:, 0], edges[:, 1], 5_000)
    ref = serial_connected_components(edges, 5_000)
    np.testing.assert_array_equal(
        canonicalize_labels(np.asarray(labels)), canonicalize_labels(ref)
    )
    assert num_components(labels) >= 25  # singletons may add more


def test_lm_training_loss_decreases():
    """Few-step LM training on the gemma smoke config: loss must drop on a
    repeated batch (end-to-end: data pipeline -> model -> optimizer)."""
    from repro.models.transformer import init_params, loss_fn

    arch = get_arch("gemma-2b")
    cfg = arch.smoke_config
    params = init_params(jax.random.PRNGKey(0), cfg)
    raw = lm_batch(4, 32, cfg.vocab_size, seed=0, step=0)
    batch = {k: jnp.asarray(v) for k, v in raw.items()}

    def data():
        while True:
            yield batch

    opt_cfg = AdamWConfig(lr=3e-3, weight_decay=0.0, warmup_steps=2)
    loop_cfg = LoopConfig(total_steps=25, checkpoint_dir=None, log_every=100)
    _, out = train(
        params,
        lambda p, b: loss_fn(p, cfg, b),
        data(),
        opt_cfg,
        loop_cfg,
    )
    first = out["history"][0]["loss"]
    last = out["final_loss"]
    assert last < first * 0.7, (first, last)


def test_serve_after_train_roundtrip(tmp_path):
    """Train briefly, checkpoint, restore into a fresh process-state, and
    decode a few tokens -- the deployment loop in miniature."""
    from repro.models.transformer import (
        init_kv_cache,
        init_params,
        loss_fn,
        serve_step,
    )
    from repro.train.checkpoint import CheckpointManager

    arch = get_arch("qwen3-4b")
    cfg = arch.smoke_config
    params = init_params(jax.random.PRNGKey(0), cfg)
    mgr = CheckpointManager(str(tmp_path), keep=1)
    mgr.save(1, {"params": params}, blocking=True)
    restored = mgr.restore(1, {"params": params})["params"]
    restored = jax.tree.map(jnp.asarray, restored)

    cache = init_kv_cache(cfg, 1, 8)
    tok = jnp.zeros((1, 1), jnp.int32)
    for i in range(8):
        logits, cache = serve_step(restored, cfg, cache, tok, jnp.int32(i))
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    assert bool(jnp.isfinite(logits).all())


_ROOT = Path(__file__).resolve().parents[1]


def _run(args, **env):
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True, timeout=120,
        cwd=_ROOT, env={**base, "JAX_PLATFORMS": "cpu",
                        "PYTHONPATH": str(_ROOT / "src"), **env},
    )


def test_chip_smoke_refuses_without_tpu():
    """The on-chip check never falls back to the CPU: it names the
    missing TPU and prints no result line."""
    r = _run([str(_ROOT / "chip_smoke.py")])
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout


_CACHE_PROBE = """
import sys
import jax, jax.numpy as jnp
from repro.launch.compile_cache import use_compile_cache
print(use_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if sys.argv[1] == "compile":
    jax.block_until_ready(jax.jit(lambda x: x + 1)(jnp.ones(3)))
"""


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "default"])
def test_compile_cache_location(tmp_path, from_env):
    """``JAX_COMPILATION_CACHE_DIR`` wins (JAX reads it itself); without
    it the cache sits at the fixed in-checkout path. The default case
    compiles nothing, so it writes nothing into the checkout."""
    from repro.launch.compile_cache import CACHE_DIR

    env = {"JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"}
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = want = str(tmp_path / "cache")
    else:
        want = str(CACHE_DIR)
    r = _run(["-c", _CACHE_PROBE, "compile" if from_env else "-"], **env)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [want, want]
    assert CACHE_DIR == _ROOT / ".jax_cache"
    if from_env:
        assert any((tmp_path / "cache").iterdir())
