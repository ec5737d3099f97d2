"""The FFA calls of the one-chip cells, at their real shapes, through the
TPU compiler from the CPU.

Each cell's own slices (from its traffic file and configuration, at 32 q / 8
kv heads, head_dim 128, the cell's tokens) go through
``lower(lowering_platforms=("tpu",))`` forward and backward, as
``tests/test_attn/test_mosaic_lowering.py`` does at small shapes. Where a
``v5e:2x2`` topology can be described, they are also compiled for one of its
chips, which is where Mosaic's VMEM limit and the core's 1 MB of SMEM are
checked: a 32768-token causal document passes the lowering and is refused
there (its plan table needs 2.1 MB; PERF.md), which the last test pins.
A kernel change that the chip's compiler would refuse fails here and costs
no chip time. All in this one file: only one process may hold libtpu.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import manifest, traffic_gen

CP1_CELLS = [
    w["name"] for w in json.load(open(
        os.path.join(manifest.ROOT, "BENCHMARK.json")))["workloads"]
    if w["chips"] == 1
]


@pytest.fixture()
def compiled_kernels(monkeypatch):
    """The real (not interpreted) kernel path, whatever the backend."""
    # import everything that binds ``_should_interpret`` by name first
    # (functional/dist_attn.py does): a module first imported under the
    # patch would keep the patched function for the rest of the process
    import magiattention_tpu.api  # noqa: F401
    from magiattention_tpu.kernels import ffa

    monkeypatch.setattr(ffa, "_should_interpret", lambda: False)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # whatever the plugin raises where it cannot
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _ffa_fwd_bwd(cell_name: str, tokens: int | None = None):
    """``(fn, shapes)``: loss-like scalar of the cell's FFA call and its
    gradients w.r.t. q, k, v, over the cell's own slices."""
    from magiattention_tpu.kernels import ffa

    cell = manifest.load_cell(manifest.ROOT, cell_name)
    cfg, traffic = cell.config, cell.traffic
    family = manifest.load_family(manifest.ROOT, cfg["family"])
    window = cfg["sliding_window"] if traffic["window"] == "config" else None
    spec = traffic_gen.make_mask(
        traffic, tokens or traffic["tokens"], window, 0,
        manifest.load_generator(manifest.ROOT, traffic["generator"]))
    qr, kr, types = family.mask_slices(spec)
    qr = np.asarray(qr.to_naive_ranges(), np.int32)
    kr = np.asarray(kr.to_naive_ranges(), np.int32)
    tm = np.asarray([t.to_int_type() for t in types], np.int32)

    def loss(q, k, v):
        out, _ = ffa.ffa_attn(q, k, v, qr, kr, tm)
        return out.astype(jnp.float32).sum()

    hq, hk, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    assert (hq, hk, d) == (32, 8, 128)  # the published widths, not a toy
    shapes = [(spec.tokens, h, d) for h in (hq, hk, hk)]
    return jax.value_and_grad(loss, argnums=(0, 1, 2)), shapes


@pytest.mark.parametrize("cell", CP1_CELLS)
def test_cell_ffa_lowers_for_tpu(compiled_kernels, cell):
    fn, shapes = _ffa_fwd_bwd(cell)
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes]
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    # forward, dq, dkv, delta: at least four Mosaic calls
    assert text.count("tpu_custom_call") >= 4


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("cell", CP1_CELLS)
def test_cell_ffa_compiles_for_v5e(compiled_kernels, one_chip, cell):
    fn, shapes = _ffa_fwd_bwd(cell)
    compiled = _compile(fn, shapes, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_a_32k_causal_document_is_refused_by_the_chip_compiler(
    compiled_kernels, one_chip
):
    """Why ``longdoc`` is 16384 tokens: at the default 256 x 512 tiles a
    32768-token causal document is 4160 work items, and their table of
    512-byte rows does not fit the core's 1 MB of SMEM. The day this
    compiles, the long cells can grow (PERF.md, open questions)."""
    fn, shapes = _ffa_fwd_bwd("nemo12b.longdoc.cp1", tokens=32768)
    with pytest.raises(jax.errors.JaxRuntimeError, match="smem"):
        _compile(fn, shapes, one_chip)
