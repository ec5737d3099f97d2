"""Nothing may hide the device: fast CPU pins for the gates a chip run
passes through (kernels/ffa._should_interpret, utils/compile_cache,
chip_smoke.py's device gate, benchmarking's peak table and device gate).
No kernel or model is compiled here."""

import os
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_support.script_loading import load_script

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))


def test_interpret_flag_on_accelerator_backend_raises(monkeypatch):
    from magiattention_tpu.kernels import ffa

    monkeypatch.setenv("MAGI_ATTENTION_PALLAS_INTERPRET", "1")
    assert ffa._should_interpret() is True  # the CPU test route
    monkeypatch.setattr(ffa.jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="MAGI_ATTENTION_PALLAS_INTERPRET"):
        ffa._should_interpret()
    monkeypatch.delenv("MAGI_ATTENTION_PALLAS_INTERPRET")
    assert ffa._should_interpret() is False  # compiled on the device


@pytest.fixture()
def cache_config():
    """Restore the two JAX options enable_persistent_cache touches."""
    saved = (
        jax.config.jax_compilation_cache_dir,
        jax.config.jax_persistent_cache_min_compile_time_secs,
    )
    yield
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])


def test_cache_dir_from_env_is_left_to_jax(monkeypatch, tmp_path, cache_config):
    from magiattention_tpu.utils import compile_cache

    jax.config.update("jax_compilation_cache_dir", "set-by-jax")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
    assert compile_cache.enable_persistent_cache() == str(tmp_path / "outside")
    # the code set no directory of its own, and created none
    assert jax.config.jax_compilation_cache_dir == "set-by-jax"
    assert not (tmp_path / "outside").exists()


def test_cache_dir_default_is_fixed_in_checkout(
    monkeypatch, tmp_path, cache_config
):
    from magiattention_tpu.utils import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "_DEFAULT_DIR", str(tmp_path / "c"))
    assert compile_cache.enable_persistent_cache() == str(tmp_path / "c")
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "c")
    # the real default: <checkout>/.jax_cache — no temp name, pid or time
    monkeypatch.undo()
    assert compile_cache._DEFAULT_DIR == os.path.join(ROOT, ".jax_cache")


@pytest.fixture()
def chip_smoke(monkeypatch):
    mod = load_script(os.path.join(ROOT, "chip_smoke.py"), "chip_smoke_mod")
    for key in mod.FORBIDDEN_ENV:
        monkeypatch.delenv(key, raising=False)

    def never(*a, **k):
        raise AssertionError("the device gate let a CPU run through")

    monkeypatch.setattr(mod, "run", never)
    return mod


def test_chip_smoke_gate_stops_cpu_before_the_model(chip_smoke, capsys):
    assert jax.default_backend() == "cpu"
    assert chip_smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert "no TPU" in err and "'cpu'" in err
    assert '"ok"' not in out  # no result line


def test_chip_smoke_last_line_is_ok_and_device_only(chip_smoke, capsys):
    import json

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    checks = {"a": {"ok": True}, "b": {"err": 1.0, "tol": 0.1, "ok": False}}
    for result, code in (
        ({"checks": {"a": checks["a"]}, "sizes": {}}, 0),
        ({"checks": checks, "sizes": {}}, 1),
    ):
        assert chip_smoke._emit(result, device, False, print) == code
        *_, report, last = capsys.readouterr().out.splitlines()
        # exactly the keys whoever runs the smoke parses; details go before
        assert json.loads(last) == {"ok": code == 0, "device": device}
        assert report.startswith("report: ") and '"sizes"' in report
    # a rehearsal never prints a result line
    assert chip_smoke._emit(result, device, True, print) == 1
    assert '"ok": false, "device"' not in capsys.readouterr().out


def test_chip_smoke_counts_a_cache_dir_that_does_not_exist_yet(
    chip_smoke, tmp_path
):
    # JAX creates the directory JAX_COMPILATION_CACHE_DIR names lazily
    assert chip_smoke._cache_entries(str(tmp_path / "not-yet")) == 0
    (tmp_path / "entry").write_text("x")
    assert chip_smoke._cache_entries(str(tmp_path)) == 1


def test_chip_smoke_refuses_device_hiding_env(chip_smoke, monkeypatch):
    monkeypatch.setenv("MAGI_ATTENTION_PALLAS_INTERPRET", "1")
    with pytest.raises(SystemExit, match="MAGI_ATTENTION_PALLAS_INTERPRET"):
        chip_smoke.main([])


def test_peak_table_raises_on_unknown_device_kind():
    import magiattention_tpu.benchmarking.perf_report as pr

    assert pr.peak_tflops("TPU v5 lite") == 197.0
    with pytest.raises(ValueError, match="no published peak"):
        pr.peak_tflops("cpu")


def test_measuring_device_stops_without_tpu():
    from magiattention_tpu.benchmarking.bench import measuring_device

    with pytest.raises(SystemExit, match="no TPU"):
        measuring_device("test")


def test_resilience_events_counted_with_telemetry_off(monkeypatch):
    from magiattention_tpu.resilience import fallback

    monkeypatch.delenv("MAGI_ATTENTION_TELEMETRY", raising=False)
    monkeypatch.setattr(fallback, "_EVENT_COUNTS", Counter())
    fallback.record_resilience_event("fallback", "kernel_lowering")
    assert fallback.resilience_event_counts() == {
        "fallback@kernel_lowering": 1
    }


# -- sites the TPU compiler refuses (chip census 2026-09-26, CHANGES.md) -----
# Lowered FOR the TPU platform from this CPU process (cross-platform
# lowering, as tests/test_attn/test_mosaic_lowering.py does for FFA).
# Paged decode and block-sparse stream K/V as (rows..., hk, d) with the
# kv-head axis blocked to 1 in the second-to-last dimension, which the
# Pallas TPU lowering rejects. The repair is a cache/chunk layout change
# (ROADMAP C3), not a constant, so the refusal is pinned here: strict xfail,
# so the day the layout is fixed these flip and must be un-marked. Until
# then selecting one of these kernels on a TPU raises — it never descends.
_REFUSED = pytest.mark.xfail(
    strict=True, raises=ValueError,
    reason="block (1, rows, 1, d) over (n, rows, hk, d): last two block "
           "dims must divide (8, 128) or equal the array's",
)


def _decode_cache(dtype, ps=128, hk=2, d=128):
    from magiattention_tpu.kernels.paged_kv import PagedKVCache

    cache = PagedKVCache.create(
        num_pages=8, page_size=ps, n_kv_heads=hk, head_dim=d, max_seqs=2,
        max_pages_per_seq=2, dtype=dtype,
    )
    return cache


@_REFUSED
@pytest.mark.parametrize("variant", ["base", "spec", "int8"])
def test_paged_decode_lowers(variant):
    from magiattention_tpu.kernels import paged_decode as pd

    cache = _decode_cache(jnp.int8 if variant == "int8" else jnp.bfloat16)
    q = jnp.zeros((2, 4, 8, 128), jnp.bfloat16)  # (slots, spec_k, hq, d)
    fn = {
        "base": lambda q, c: pd.paged_decode_attn(
            q[:, 0], c, interpret=False),
        "spec": lambda q, c: pd.paged_decode_attn_spec(
            q, c, interpret=False),
        "int8": lambda q, c: pd.paged_decode_attn_int8(
            q[:, 0], c, interpret=False),
    }[variant]
    jax.jit(fn).trace(q, cache).lower(lowering_platforms=("tpu",))


@_REFUSED
def test_block_sparse_lowers():
    from magiattention_tpu.kernels.block_sparse import block_sparse_attn

    s, hk, g, d = 256, 2, 4, 128
    starts = np.arange(0, s - 64 + 1, 32, dtype=np.int32)
    idx = jnp.zeros((hk, s // 16, 1), jnp.int32)
    q, k, v = (jnp.zeros((s, h, d), jnp.bfloat16) for h in (hk * g, hk, hk))
    jax.jit(
        lambda q, k, v: block_sparse_attn(
            q, k, v, idx, starts, block_len=64, d_stride=32,
            block_size_q=16, interpret=False,
        )[0]
    ).trace(q, k, v).lower(lowering_platforms=("tpu",))
