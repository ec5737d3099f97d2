"""Flax (linen) integration example (ref: examples/transformers — drop-in
attention integration with a host framework).

Shows the "no call-site changes" property: a linen transformer whose
attention layer routes through MagiAttention CP (`calc_attn`) — the module
API stays pure-functional linen; the runtime key is static configuration.

Run (no TPU needed — virtual CPU mesh):

    python examples/flax_integration.py --devices 4 --steps 3
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--seqlen", type=int, default=256)
    args = ap.parse_args()

    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")

    import flax.linen as nn
    import jax.numpy as jnp
    import numpy as np
    import optax
    from flax.training.train_state import TrainState
    from jax.sharding import Mesh

    from magiattention_tpu.api import (
        calc_attn,
        dispatch,
        get_position_ids,
        magi_attn_flex_key,
    )

    S = args.seqlen
    mesh = Mesh(np.array(jax.devices()[: args.devices]), axis_names=("cp",))
    attn_key = magi_attn_flex_key(
        [[0, S]], [[0, S]], ["causal"], S, S,
        mesh=mesh, cp_axis="cp", chunk_size=max(S // 16, 16),
    )

    DIM, HEADS, KV_HEADS, HDIM, VOCAB = 128, 4, 2, 32, 256

    class MagiAttentionLayer(nn.Module):
        """Linen attention block running on the dispatched CP layout."""

        @nn.compact
        def __call__(self, x):  # x: (shard, DIM) dispatched rows
            pos = get_position_ids(attn_key)
            q = nn.Dense(HEADS * HDIM, use_bias=False, name="wq")(x)
            k = nn.Dense(KV_HEADS * HDIM, use_bias=False, name="wk")(x)
            v = nn.Dense(KV_HEADS * HDIM, use_bias=False, name="wv")(x)
            q = q.reshape(-1, HEADS, HDIM)
            k = k.reshape(-1, KV_HEADS, HDIM)
            v = v.reshape(-1, KV_HEADS, HDIM)
            del pos  # rope omitted for brevity
            out, _ = calc_attn(q, k, v, attn_key)
            out = out.reshape(-1, HEADS * HDIM)
            return nn.Dense(DIM, use_bias=False, name="wo")(out)

    class TinyModel(nn.Module):
        @nn.compact
        def __call__(self, tokens):  # (S,) natural order
            # dispatch the ids, then embed: rows are born on their chip (the
            # other order builds all S rows everywhere, then all-reduces them)
            x = nn.Embed(VOCAB, DIM, name="embed")(dispatch(tokens, attn_key))
            x = x + MagiAttentionLayer(name="attn")(nn.LayerNorm()(x))
            h = nn.Dense(4 * DIM, name="up")(nn.LayerNorm()(x))
            x = x + nn.Dense(DIM, name="down")(nn.gelu(h))
            return nn.Dense(VOCAB, name="lm_head")(nn.LayerNorm()(x))

    model = TinyModel()
    rng = np.random.default_rng(0)
    tokens0 = jnp.asarray(
        rng.integers(0, VOCAB, S).astype(np.int32)
    )
    params = model.init(jax.random.key(0), tokens0)
    state = TrainState.create(
        apply_fn=model.apply, params=params, tx=optax.adamw(1e-3)
    )

    @jax.jit
    def step(state, tokens, labels):
        def loss_fn(p):
            logits = state.apply_fn(p, tokens)  # dispatched order
            labels_d = dispatch(labels, attn_key)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(
                logp, jnp.maximum(labels_d, 0)[:, None], axis=-1
            )[:, 0]
            valid = labels_d >= 0
            return jnp.sum(jnp.where(valid, nll, 0.0)) / jnp.maximum(
                jnp.sum(valid), 1
            )

        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        return state.apply_gradients(grads=grads), loss

    for i in range(args.steps):
        tokens = rng.integers(0, VOCAB, S).astype(np.int32)
        labels = np.concatenate([tokens[1:], [-1]]).astype(np.int32)
        state, loss = step(
            state, jnp.asarray(tokens), jnp.asarray(labels)
        )
        print(f"step {i}: loss {float(loss):.4f}", flush=True)
    print("done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
