"""Read the timed recurrence's numbers over seeds and under its controls,
for the limits of ``reference_solar_open2.TIMED_CHECKS``.

    python -m cellbench.kda_timed_probe [--workload solaropen2.packed.cp1]
        [--seed <first>] [--seeds 4] [--controls a,b] [--rehearse-cpu N]

One process: the cell's timed mask at its own token count, no parameters
and no step. For ``--seeds`` seeds it runs the program's kernel
(``family_solar_open2.timed_program``) against the recurrence token by token
(``timed_reference``) and prints what ``correct`` compares; then, on the
first seed, each control of ``--controls``, which has to come out as not
correct by at least one name:

* ``state_bf16``: the state the kernels carry from chunk to chunk (and its
  gradient, walking back) rounded to bf16 when stored;
* ``carry_across``: the carry flags (``ssd.segment_rows`` row 1) set for the
  tokens of one document that starts inside a chunk after the first (the
  start nearest its chunk's own), from its start to the chunk's end: the
  state carried into that chunk crosses the start;
* ``beta_single``: the kernel given ``beta / 2``, beta not doubled;
* ``fp8_inputs``: q, k, v and the cotangent rounded by
  ``lax.reduce_precision`` to 4 exponent and 3 mantissa bits, below the
  configuration's bf16.

``--model`` reads the model check at ``check_tokens_per_chip`` instead
(``reference_solar_open2.CHECKS``, the family's check program against its
reference), the controls planted in every KDA layer of the program
(``beta_single`` there is ``kda_neg_eigval`` off). Every line is JSON: the
readings, each name's limit, the names that failed. Not part of a run of the
benchmark.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys

CONTROLS = ("state_bf16", "carry_across", "beta_single", "fp8_inputs")


class _Rounded:
    """A kernel's scratch ref whose stores are rounded to bf16."""

    def __init__(self, ref):
        self.ref, self.shape, self.dtype = ref, ref.shape, ref.dtype

    def __getitem__(self, index):
        return self.ref[index]

    def __setitem__(self, index, value):
        import jax.numpy as jnp

        self.ref[index] = value.astype(jnp.bfloat16).astype(self.dtype)


def _carry_in_bf16(kda):
    """Patch both bodies so that their last ref (the carried state, its
    gradient) is stored in bf16; returns the undo."""
    saved = kda._kda_fwd_kernel, kda._kda_bwd_kernel

    def wrap(body):
        @functools.wraps(body)
        def rounded(*refs, **kw):
            return body(*refs[:-1], _Rounded(refs[-1]), **kw)
        return rounded

    kda._kda_fwd_kernel, kda._kda_bwd_kernel = map(wrap, saved)

    def undo():
        kda._kda_fwd_kernel, kda._kda_bwd_kernel = saved
    return undo


def _faulty_scan(scan, control, q, k, v, g, beta, seg_rows):
    """``kda.kda_scan`` with ``control`` planted: ``carry_across`` the carry
    flags set from one document start inside a chunk after the first (the
    start nearest its chunk's own) to that chunk's end; ``fp8_inputs`` q, k
    and v rounded to 4 exponent and 3 mantissa bits."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from magiattention_tpu.kernels import kda

    if control == "fp8_inputs":
        fp8 = functools.partial(
            jax.lax.reduce_precision, exponent_bits=4, mantissa_bits=3)
        q, k, v = (fp8(x.astype(jnp.float32)).astype(x.dtype)
                   for x in (q, k, v))
    else:
        seg_rows = _carry_across(seg_rows, kda.CHUNK, np, jnp)
    return scan(q, k, v, g, beta, seg_rows)


def _carry_across(rows, chunk, np, jnp):
    """``rows`` with the carry flags set from one document start inside a
    chunk after the first (the start nearest its chunk's own: the state it
    crosses is the least decayed) to that chunk's end; traced or not."""
    at = jnp.arange(rows.shape[1])
    ids = rows[0]
    inside = (ids == at) & (at % chunk != 0) & (at > chunk)
    s = jnp.argmin(jnp.where(inside, at % chunk, chunk))
    end = (s // chunk + 1) * chunk
    return rows.at[1].set(jnp.where((at >= s) & (at < end), 1.0, rows[1]))


def main(argv: list[str]) -> int:
    from cellbench import manifest, run

    ap = argparse.ArgumentParser(prog="python -m cellbench.kda_timed_probe")
    ap.add_argument("--workload", default="solaropen2.packed.cp1")
    ap.add_argument("--seed", type=int, default=2147480000)
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ap.add_argument("--rehearse-cpu", type=int, default=0)
    ap.add_argument("--model", action="store_true",
                    help="the model check at check_tokens_per_chip instead")
    args = ap.parse_args(argv)
    run.refuse_hidden_device_env()
    if args.rehearse_cpu and "jax" not in sys.modules:
        os.environ["JAX_PLATFORMS"] = "cpu"
    cell = manifest.load_cell(manifest.ROOT, args.workload)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from cellbench import reference, traffic_gen
    from magiattention_tpu.kernels import kda

    family = manifest.load_family(manifest.ROOT, cell.config["family"])
    cfg, tokens, window, check_tokens = run.cell_sizes(
        cell, family, args.rehearse_cpu)
    spec = traffic_gen.make_mask(
        cell.traffic, tokens, window, args.seed,
        manifest.load_generator(manifest.ROOT, cell.traffic["generator"]))
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("cp",))
    sharding = NamedSharding(mesh, P())
    mcfg = family.model_config(cfg)
    checks = family.reference_solar_open2.TIMED_CHECKS
    run_state = family._RUN

    def reading(seed, control=None) -> dict:
        run_state.clear()
        run_state.update(seed=seed, timed_spec=spec)
        (q, k, v, g, beta, do), rows, _ = family._timed_inputs(
            family.timed_draw_config(mcfg), sharding)
        undo = None
        if control == "state_bf16":
            undo = _carry_in_bf16(kda)
        elif control == "carry_across":
            rows = _carry_across(rows, kda.CHUNK, np, jnp)
        elif control == "beta_single":
            beta = beta / 2
        elif control == "fp8_inputs":
            fp8 = functools.partial(
                jax.lax.reduce_precision, exponent_bits=4, mantissa_bits=3)
            q, k, v, do = (fp8(x.astype(jnp.float32)).astype(x.dtype)
                           for x in (q, k, v, do))
        try:
            got = jax.device_get(jax.jit(  # traced anew: a control patches
                lambda *a: family._timed_program.__wrapped__(*a))(
                q, k, v, g, beta, do, rows))
        finally:
            if undo:
                undo()
        got = {f"kda_timed_{n}": x for n, x in zip(family.TIMED, got)}
        ref = family.timed_reference(cfg, sharding)
        out = reference.compare(got, ref, checks, targets=1)
        return {"seed": seed, "control": control,
                "readings": {n: c["err"] for n, c in out.items()},
                "limits": {n: c["tol"] for n, c in out.items()},
                "failed": [n for n, c in out.items() if not c["ok"]]}

    def model_reading(seed, control=None) -> dict:
        """The model check at the cell's check tokens, under ``control``
        (the same four, applied to every KDA layer of the program)."""
        check_spec = traffic_gen.make_mask(
            cell.traffic, check_tokens, window, seed,
            manifest.load_generator(manifest.ROOT,
                                    cell.traffic["generator"]))
        toks, labels = (jnp.asarray(x) for x in traffic_gen.token_batches(
            check_spec, cfg["vocab_size"], seed, 1)[0])
        mcfg_c = mcfg
        scan, undo = kda.kda_scan, None
        if control == "beta_single":
            mcfg_c = dataclasses.replace(mcfg, kda_neg_eigval=False)
        elif control == "state_bf16":
            undo = _carry_in_bf16(kda)
        elif control in ("carry_across", "fp8_inputs"):
            kda.kda_scan = functools.partial(_faulty_scan, scan, control)
        try:
            params = family.init_params(mcfg, mesh, seed)
            got = jax.device_get(family.check_program(
                mcfg_c, family.make_key(check_spec, mesh))(
                params, toks, labels))
        finally:
            kda.kda_scan = scan
            if undo:
                undo()
        ref = jax.device_get(family.reference(
            params, cfg, toks, labels, check_spec))
        out = reference.compare(
            got, ref, family.reference_solar_open2.CHECKS,
            targets=int((np.asarray(labels) >= 0).sum()))
        return {"seed": seed, "control": control, "model": True,
                "readings": {n: c["err"] for n, c in out.items()},
                "limits": {n: c["tol"] for n, c in out.items()},
                "failed": [n for n, c in out.items() if not c["ok"]]}

    if args.model:
        reading = model_reading  # noqa: F811
    for i in range(args.seeds):
        print(json.dumps(reading(args.seed + i)), flush=True)
    for control in filter(None, args.controls.split(",")):
        print(json.dumps(reading(args.seed, control)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
