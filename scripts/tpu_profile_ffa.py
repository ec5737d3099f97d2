"""Capture a jax profiler trace of the FFA fwd kernel on TPU and print the
top device ops by self-time (parsed locally from the trace protobuf — no
tensorboard needed).

    python scripts/tpu_profile_ffa.py [trace_dir]
"""
import glob
import gzip
import json
import os
import sys

# hot-path named scopes (utils/profiling.py) must be on BEFORE the
# package traces anything, so phase attribution shows up in the events
os.environ["MAGI_ATTENTION_PROFILE_MODE"] = "1"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

import jax.numpy as jnp
import numpy as np


def main() -> int:
    from magiattention_tpu.benchmarking.bench import measuring_device

    trace_dir = sys.argv[1] if len(sys.argv) > 1 else "/tmp/ffa_trace"
    print("device:", measuring_device("tpu_profile_ffa"), flush=True)

    from magiattention_tpu.kernels.ffa import ffa_attn

    S, HQ, HK, D = 8192, 16, 8, 128
    rng = np.random.default_rng(0)
    q0 = jnp.asarray(rng.standard_normal((S, HQ, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((S, HK, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((S, HK, D)), jnp.bfloat16)
    qr = np.array([[0, S]], np.int32)
    tm = np.array([1], np.int32)

    w = jnp.asarray(rng.standard_normal((S, HQ, D)), jnp.bfloat16)

    def loss(q):
        o, _lse = ffa_attn(q, k, v, qr, qr, tm, block_q=512, block_k=512)
        return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32))

    grad = jax.grad(loss)

    @jax.jit
    def run(q):
        # fwd+bwd chained: the trace must attribute BOTH directions (the
        # headline metric is fwd+bwd; r3 judged the gap is not bwd-only)
        def body(c, _):
            return grad(c).astype(jnp.bfloat16), None

        return jax.lax.scan(body, q, None, length=4)[0]

    jax.block_until_ready(run(q0))  # compile outside the trace
    with jax.profiler.trace(trace_dir):
        jax.block_until_ready(run(q0))

    # parse the trace: sum durations per event name on device lines
    files = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                  recursive=True),
        key=os.path.getmtime,
    )
    if not files:
        print("no trace files under", trace_dir)
        return 1
    with gzip.open(files[-1], "rt") as f:
        trace = json.load(f)
    pid_names = {}
    for e in trace.get("traceEvents", []):
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pid_names[e["pid"]] = e["args"].get("name", "")
    durs: dict[str, float] = {}
    for e in trace.get("traceEvents", []):
        if e.get("ph") == "X" and "TPU" in pid_names.get(e.get("pid"), ""):
            durs[e["name"]] = durs.get(e["name"], 0.0) + e.get("dur", 0.0)
    total = sum(durs.values())
    print(f"total device time: {total/1e3:.2f} ms (4 chained fwd+bwd)")
    for name, d in sorted(durs.items(), key=lambda kv: -kv[1])[:15]:
        print(f"  {d/1e3:9.3f} ms  {d/max(total,1)*100:5.1f}%  {name[:90]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
