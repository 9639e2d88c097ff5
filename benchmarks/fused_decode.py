"""Fused vs staged CEAZ decode throughput (the read half of Fig 4).

The write path got its fused device pipeline in PR 1; this lane measures
the symmetric read path on the proxy corpus:

  * staged — the host reference decompressor: python loop over chunks,
    numpy table decode per chunk (`use_fused=False`);
  * mega   — the fused route: the `ceaz_chunk_dec` decode megakernel,
    walk + outlier patch + inverse dual-quant in one launch.

Both decode the SAME compressed streams and are bit-identical
(tests/test_fused_decode.py, tests/test_full_grid.py), so the
comparison is pure throughput. The fused column must dominate staged
— asserted at the end, since the nightly CI lane runs this as the
decode-throughput acceptance gate. jit compilation is warmed before
timing.
"""
from __future__ import annotations

import numpy as np

from repro.core import CEAZ, CEAZConfig, default_offline_codebook

from .common import corpus, emit, time_call


def _comp(offline_cb, **kw):
    return CEAZ(CEAZConfig(mode="rel", eb=1e-4, chunk_bytes=1 << 21,
                           predictor="lorenzo", **kw),
                offline_codebook=offline_cb)


def run():
    offline_cb = default_offline_codebook()
    variants = {
        "staged": _comp(offline_cb, backend="jax", use_fused=False),
        "mega": _comp(offline_cb, use_fused=True),
    }
    rows = []
    totals = {k: [0.0, 0] for k in variants}
    for name, arr in corpus():
        arr = arr.astype(np.float32)
        c = variants["staged"].compress(arr)
        for vname, comp in variants.items():
            rec = comp.decompress(c)                 # warm jit caches
            assert rec.shape == arr.shape
            _, t = time_call(comp.decompress, c, repeats=3)
            rows.append(dict(kind="dataset", dataset=name, variant=vname,
                             mb=arr.nbytes / 1e6, seconds=t,
                             throughput_mbs=arr.nbytes / t / 1e6))
            totals[vname][0] += t
            totals[vname][1] += arr.nbytes
    tp = {k: v[1] / v[0] / 1e6 for k, v in totals.items()}
    speedup = tp["mega"] / tp["staged"]
    rows.append(dict(kind="summary", **{f"tp_{k}": v for k, v in tp.items()},
                     fused_over_staged=speedup))
    emit("fused_decode", rows,
         us_per_call=float(totals["mega"][0] * 1e6 / max(len(rows) - 1, 1)),
         derived=(f"mega={tp['mega']:.0f}MB/s;"
                  f"staged={tp['staged']:.0f}MB/s;"
                  f"speedup={speedup:.2f}x"))
    assert speedup >= 1.0, (
        f"megakernel decode slower than staged ({speedup:.2f}x)")
    return rows


if __name__ == "__main__":
    run()
