"""The FFA calls of the one-chip cells, at their real shapes, through the
TPU compiler from the CPU.

Each cell's own slices (from its traffic file, at the cell's tokens) and the
heads its family file says attend (``ffa_calls`` of the configuration file
as it stands, no toy laid over) go through
``lower(lowering_platforms=("tpu",))`` forward and backward, as
``tests/test_attn/test_mosaic_lowering.py`` does at small shapes. Where a
``v5e:2x2`` topology can be described, they are also compiled for one of its
chips, which is where Mosaic's VMEM limit and the core's 1 MB of SMEM are
checked: a 32768-token causal document passes the lowering and is refused
there (its plan table needs 2.1 MB; PERF.md), which the last test pins.
A kernel change that the chip's compiler would refuse fails here and costs
no chip time. All in this one file: only one process may hold libtpu.

Nothing here knows a head layout, a backward or a family: the last test
runs these per-cell tests, with the others of this directory, on a copy of
the benchmark that has two cells of other shapes added (``foreign_cells.py``).
"""

import json
import os
import subprocess
import sys
import xml.etree.ElementTree as ET

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import foreign_cells
from cellbench import family_llama, kernel_times, manifest, run, traffic_gen

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


def _cell_and_family(cell_name: str):
    cell = manifest.load_cell(manifest.ROOT, cell_name)
    return cell, manifest.load_family(manifest.ROOT, cell.config["family"])


def _ffa_fwd_bwd(cell_name: str, tokens: int | None = None):
    """``[(fn, shapes), ...]``: for each distinct ``(hq, hk, d_qk, d_v)``
    among the layers the cell's family says attend, a loss-like scalar of
    the FFA call and its gradients w.r.t. q, k, v over the cell's own
    slices, with the shapes of q, k, v."""
    from magiattention_tpu.kernels import ffa

    cell, family = _cell_and_family(cell_name)
    cfg, cell_tokens, window, _ = run.cell_sizes(cell, family, 0)
    assert cfg == cell.config  # the file's widths, no TOY laid over
    spec = traffic_gen.make_mask(
        cell.traffic, tokens or cell_tokens, window, 0,
        manifest.load_generator(manifest.ROOT, cell.traffic["generator"]))
    # the program's public mask compilers, which are no family's
    qr, kr, types = family_llama.mask_slices(spec)
    qr = np.asarray(qr.to_naive_ranges(), np.int32)
    kr = np.asarray(kr.to_naive_ranges(), np.int32)
    tm = np.asarray([t.to_int_type() for t in types], np.int32)

    def loss(q, k, v):
        out, _ = ffa.ffa_attn(q, k, v, qr, kr, tm)
        return out.astype(jnp.float32).sum()

    groups = sorted({
        (g["hq"], g["hk"], g["d_qk"], g["d_v"])
        for g in family.ffa_calls(cfg) if g["layers"]})
    assert groups, f"no layer of {cell_name} attends"
    fn = jax.value_and_grad(loss, argnums=(0, 1, 2))
    return [
        (fn, [(spec.tokens, hq, d_qk), (spec.tokens, hk, d_qk),
              (spec.tokens, hk, d_v)])
        for hq, hk, d_qk, d_v in groups]


@pytest.mark.parametrize("cell", CP1_CELLS)
def test_cell_ffa_lowers_for_tpu(compiled_kernels, cell):
    family = _cell_and_family(cell)[1]
    for fn, shapes in _ffa_fwd_bwd(cell):
        args = [jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes]
        traced = jax.jit(fn).trace(*args)
        kernels = family.pallas_kernels(traced.jaxpr)
        kinds = {kernel_times.kind_of(kernel_times.PREFIX + body)
                 for body in kernels}
        mode = "fused" if "bwd_fused" in kinds else "split"
        said = (f"{cell} at q, k, v {shapes}: backward {mode} (the program "
                f"says {family.what_ran()['ffa_bwd_mode']}), bodies "
                f"{sorted(kernels)}")
        print(said)
        assert not any(kernels.values()), said  # none interpreted
        # a forward body, delta, and the backward either split or fused
        assert {"fwd", "delta"} <= kinds, said
        assert {"bwd_dq", "bwd_dkv"} <= kinds or "bwd_fused" in kinds, said
        text = traced.lower(lowering_platforms=("tpu",)).as_text()
        assert text.count("tpu_custom_call") >= 3, said


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("cell", CP1_CELLS)
def test_cell_ffa_compiles_for_v5e(compiled_kernels, one_chip, cell):
    for fn, shapes in _ffa_fwd_bwd(cell):
        compiled = _compile(fn, shapes, one_chip)
        assert "tpu_custom_call" in compiled.as_text(), shapes


def test_a_32k_causal_document_is_refused_by_the_chip_compiler(
    compiled_kernels, one_chip
):
    """Why ``longdoc`` is 16384 tokens: at the default 256 x 512 tiles a
    32768-token causal document is 4160 work items, and their table of
    512-byte rows does not fit the core's 1 MB of SMEM. The day this
    compiles, the long cells can grow (PERF.md, open questions)."""
    [(fn, shapes)] = _ffa_fwd_bwd("nemo12b.longdoc.cp1", tokens=32768)
    with pytest.raises(jax.errors.JaxRuntimeError, match="smem"):
        _compile(fn, shapes, one_chip)


# What each added cell has to have been through, by the test's name.
PER_CELL_TESTS = (
    "test_cell_ffa_lowers_for_tpu", "test_cell_ffa_compiles_for_v5e",
    "test_cell_resolves", "test_every_cell_rehearses_end_to_end",
    "test_a_run_without_a_tpu_fails_and_prints_no_result")


def test_foreign_cells_pass_the_per_cell_tests(tmp_path):
    """The real per-cell tests, unmodified, on a copy of the benchmark with
    two cells they were not written for (``foreign_cells.py``): another
    family with attention in some layers, and g = 1 at 30 heads with a
    fused backward. ``python -m pytest`` from the copy puts it first on
    ``sys.path``, so ``manifest.ROOT`` is the copy; the program comes from
    this checkout. Here and not in a file of its own because the child
    describes the v5e topology while this process holds libtpu: where the
    environment lets two processes load it (the tier-1 command does) the
    child compiles for the chip too, elsewhere it finds the lock taken and
    its two compile cases skip."""
    cells = foreign_cells.build_root(tmp_path)
    # the child is a pytest of its own: none of this one's options or ids
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    env["PYTHONPATH"] = os.pathsep.join(
        [manifest.ROOT, *filter(None, [env.get("PYTHONPATH")])])
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/test_cellbench", "-q",
         "-p", "no:cacheprovider", "-k", "mixer or probe",
         "--junitxml", "foreign.xml"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    # 0: every selected case passed or skipped
    assert done.returncode == 0, done.stdout[-6000:] + done.stderr[-2000:]
    cases = list(ET.parse(tmp_path / "foreign.xml").iter("testcase"))
    skipped = {
        case.get("name"): case.find("skipped").get("message")
        for case in cases if case.find("skipped") is not None}
    # only a compile for the chip may skip, and only for want of a topology
    assert all(
        name.startswith("test_cell_ffa_compiles_for_v5e[")
        and "no v5e:2x2 topology can be described" in why
        for name, why in skipped.items()), skipped
    if os.environ.get("ALLOW_MULTIPLE_LIBTPU_LOAD") == "1":
        assert not skipped, skipped
    for cell in cells:
        for test in PER_CELL_TESTS:
            assert any(
                case.get("name").startswith(test + "[")
                and cell in case.get("name") for case in cases), (test, cell)
