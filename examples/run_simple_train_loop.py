"""Minimal library-level training loop — the 50-line starter.

Parity with /root/reference/examples/run_simple_mcore_train_loop.py:
build a tiny GPT from the core library, run a few steps on mock data,
save and restore a checkpoint. TPU-first shape: one mesh, one jitted
train step, Orbax round trip. Runs anywhere:

  JAX_PLATFORMS=cpu python examples/run_simple_train_loop.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax

from megatronapp_tpu.config.parallel_config import ParallelConfig
from megatronapp_tpu.config.training_config import OptimizerConfig
from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.data.mock import mock_batches
from megatronapp_tpu.models.gpt import gpt_loss, init_gpt_params
from megatronapp_tpu.parallel.mesh import build_mesh
from megatronapp_tpu.training.checkpointing import CheckpointManager
from megatronapp_tpu.training.optimizer import get_optimizer
from megatronapp_tpu.training.train import reshape_global_batch
from megatronapp_tpu.training.train_state import setup_train_state
from megatronapp_tpu.training.train_step import make_train_step

SEQ = 64

cfg = TransformerConfig(num_layers=2, hidden_size=64,
                        num_attention_heads=4, vocab_size=128,
                        max_position_embeddings=SEQ)
ctx = build_mesh(ParallelConfig(), devices=jax.devices()[:1])
opt_cfg = OptimizerConfig(lr=1e-3)
optimizer = get_optimizer(opt_cfg, 10)
state, shardings, _ = setup_train_state(
    jax.random.PRNGKey(0), lambda k: init_gpt_params(k, cfg),
    optimizer, ctx)

step = make_train_step(
    lambda p, m: gpt_loss(p, m["tokens"], m["labels"], m["loss_mask"],
                          cfg, ctx=ctx),
    optimizer, opt_cfg, ctx, shardings, 10)

batches = mock_batches(SEQ, cfg.vocab_size, batch_size=4, seed=0)
with ctx.mesh:
    for it in range(10):
        state, metrics = step(state, reshape_global_batch(next(batches), 1))
        print(f"iter {it + 1}: loss {float(metrics['loss']):.4f}")

    # Checkpoint round trip (reference dist_checkpointing save/load).
    ckpt_dir = tempfile.mkdtemp(prefix="simple_ckpt_")
    mngr = CheckpointManager(ckpt_dir, async_save=False)
    mngr.save(10, jax.device_get(state), force=True)
    mngr.wait()
    restored = mngr.restore(state)
    mngr.close()
    assert int(jax.device_get(restored["step"])) == 10
    print(f"checkpoint round trip OK ({ckpt_dir})")
