"""The model's forward pass as every entry point runs it: one compiled
program a call.

Called eagerly, `gpt_forward` compiles each primitive of its trace on its
own the first time the process meets it at a shape: 8.2 s for tiny Granite's
two rows of 40 where the jitted pass takes 1.5 s (my sandbox, PR 62). The
logits differ by a float32 rounding (1.6e-9 on logits of std 5e-4)."""
import jax
import numpy as np

from megatronapp_tpu.models import gpt


def gpt_forward(params, tokens, cfg, **kw):
    return jax.jit(lambda p, t: gpt.gpt_forward(p, t, cfg, **kw))(
        params, tokens)


_FORWARDS = []      # (cfg, its jitted forward): a config is not hashable


def greedy_oracle(params, cfg, prompt, n):
    """The dense model's greedy continuation of `prompt` by `n` tokens, a
    full forward pass a token: the reference the engines' streams are held
    to. Every pass runs over a row of one length, zeros behind the tokens
    so far (causal: what lies behind a position does not reach it), and
    rows are sized in eights, so the passes and most prompts share one
    program where a pass a length compiled one each."""
    forward = next((f for c, f in _FORWARDS if c == cfg), None)
    if forward is None:
        forward = jax.jit(lambda p, t: gpt.gpt_forward(p, t, cfg)[0])
        _FORWARDS.append((cfg, forward))
    prompt = np.asarray(prompt)
    size = len(prompt) + n
    size = max(size, min(-(-size // 8) * 8, cfg.max_position_embeddings))
    row = np.zeros((1, size), prompt.dtype)
    row[0, :len(prompt)] = prompt
    for at in range(len(prompt), len(prompt) + n):
        row[0, at] = int(np.argmax(forward(params, row)[0, at - 1]))
    return row[0, :len(prompt) + n].tolist()
