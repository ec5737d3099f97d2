"""Muon-style QK-Clip on the distributed max_logits (ref blog
docs/source/blog/muon_qk_clip.md — the reference exposes
``meta.max_logits`` and leaves the clip to user code; this example shows
the full loop working against the CP engine).

Per train step:

1. project q/k/v, run distributed attention with
   ``return_max_logits=True`` — ``meta.max_logits`` is the per-q-head
   max of the SCALED logits over the whole (cp-sharded) attention
   matrix, all-reduced MAX across ranks;
2. take an optimizer step;
3. QK-Clip: for every head whose max logit exceeds the threshold tau,
   scale W_q and W_k by sqrt(tau / max_logit) — logits are bilinear in
   (W_q, W_k), so the head's max logit drops to ~tau while the softmax
   direction is preserved.

Run: ``python examples/qk_clip_muon.py``. The printout shows exploding
heads (seeded with oversized W_q) being pulled back under tau within a
couple of steps while loss keeps improving.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

# the 8-device virtual CPU mesh by default (kernels interpreted); pass
# --tpu to run on the attached TPUs with the kernels compiled
if "--tpu" not in sys.argv:
    jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from magiattention_tpu.api import calc_attn, dispatch, magi_attn_flex_key

S, H, D, DM = 512, 4, 32, 128
TAU = 12.0  # QK-Clip threshold on the scaled max logit
LR = 0.05


def main() -> None:
    mesh = Mesh(np.array(jax.devices()[:4]), ("cp",))
    key = magi_attn_flex_key(
        [[0, S]], [[0, S]], [1], S, S, mesh=mesh, cp_axis="cp",
        chunk_size=32,
    )
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((S, DM)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((S, H, D)), jnp.float32)
    params = {
        # W_q deliberately oversized: heads start with exploding logits
        "wq": jnp.asarray(rng.standard_normal((DM, H, D)) * 1.5,
                          jnp.float32),
        "wk": jnp.asarray(rng.standard_normal((DM, H, D)) * 0.3,
                          jnp.float32),
        "wv": jnp.asarray(rng.standard_normal((DM, H, D)) * 0.3,
                          jnp.float32),
    }
    xd = dispatch(x, key)
    yd = dispatch(y, key)

    def forward(p, xd):
        q = jnp.einsum("sd,dhe->she", xd, p["wq"])
        k = jnp.einsum("sd,dhe->she", xd, p["wk"])
        v = jnp.einsum("sd,dhe->she", xd, p["wv"])
        out, meta = calc_attn(q, k, v, key, return_max_logits=True)
        return out, meta.max_logits

    def loss_fn(p, xd, yd):
        out, ml = forward(p, xd)
        return jnp.mean((out - yd) ** 2), ml

    grad_fn = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))

    @jax.jit
    def qk_clip(p, max_logits):
        # eta < 1 only for heads above the threshold; sqrt splits the
        # correction evenly between W_q and W_k (logits ~ W_q W_k^T)
        eta = jnp.minimum(1.0, TAU / jnp.maximum(max_logits, 1e-6))
        scale = jnp.sqrt(eta)[None, :, None]
        return {**p, "wq": p["wq"] * scale, "wk": p["wk"] * scale}

    for step in range(6):
        (loss, max_logits), grads = grad_fn(params, xd, yd)
        params = jax.tree.map(lambda w, g: w - LR * g, params, grads)
        clipped = int(jnp.sum(max_logits > TAU))
        params = qk_clip(params, max_logits)
        print(
            f"step {step}: loss={float(loss):.4f} "
            f"max_logits={np.array2string(np.asarray(max_logits), precision=1)} "
            f"-> clipped {clipped}/{H} heads"
        )

    _, ml = jax.jit(forward)(params, xd)
    assert bool(jnp.all(ml <= TAU * 1.05)), ml
    print(f"all heads under tau={TAU} after QK-Clip. OK")


if __name__ == "__main__":
    main()
