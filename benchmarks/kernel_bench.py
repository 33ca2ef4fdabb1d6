"""Kernel micro-benchmarks: Pallas (interpret) vs jnp reference wall time +
the structural VMEM working-set check for the TPU BlockSpecs.

On CPU the interpret-mode kernel is *slower* than fused XLA jnp — the
deliverable here is correctness parity plus the VMEM footprint audit that
matters on the real target (block bytes must fit the ~16 MiB/core VMEM)."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row
from repro.core import distributions as d
from repro.core import fitting
from repro.core.pdf_error import histogram as hist_jnp
from repro.core.regions import Window
from repro.core.distributions import moments_from_values
from repro.kernels.hist import histogram as hist_kernel
from repro.kernels.moments import moments as moments_kernel


def _time(f, *args, reps=11):
    """Best-of-reps: timing noise on a shared container is strictly additive
    (bandwidth contention hits the one-hot rows up to ~1.7x), so the min is
    the stable estimator the run.py --check gate can diff across runs."""
    jax.block_until_ready(f(*args))  # warmup/compile
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        samples.append(time.perf_counter() - t0)
    return min(samples)


def vmem_bytes(bp: int, bn: int, num_bins: int = 64) -> int:
    # values tile + accumulators + onehot intermediate (f32)
    return bp * bn * 4 + bp * 8 * 4 + bp * bn * num_bins * 4 // 16


def run(quick: bool = True):
    rows = []
    p, n = (256, 1000) if quick else (2048, 10000)
    v = jnp.asarray(np.random.default_rng(0).normal(3000, 10, (p, n)), jnp.float32)

    t_ref = _time(jax.jit(lambda x: moments_from_values(x)), v)
    t_ker = _time(lambda x: moments_kernel(x), v)
    rows.append(Row("kernel/moments_ref_jnp", t_ref * 1e6, f"P={p} n={n}"))
    rows.append(Row("kernel/moments_pallas_interpret", t_ker * 1e6,
                    "correctness: tests/test_kernels.py"))

    vmin, vmax = v.min(1), v.max(1)
    t_ref = _time(jax.jit(lambda x, a, b: hist_jnp(x, a, b, 64)), v, vmin, vmax)
    t_ker = _time(lambda x, a, b: hist_kernel(x, a, b, 64), v, vmin, vmax)
    rows.append(Row("kernel/hist_ref_jnp", t_ref * 1e6, ""))
    rows.append(Row("kernel/hist_pallas_interpret", t_ker * 1e6, ""))

    # End-to-end ComputePDF&Error: the fused single-launch path (kernels/
    # fitpdf) vs the chained two-pass kernel path (moments kernel + hist
    # kernel + XLA masses/error). Same moments->select semantics; the fused
    # rows must beat two-pass by >= 1.5x (fused-fit issue acceptance).
    def _fit_fn(backend_name, types):
        backend = fitting.get_fit_backend(backend_name, 64)

        @jax.jit
        def run_fit(x):
            m = backend.moments(x)
            r = backend.fit_all(x, m, types, 64, "fused")
            return r.type_idx, r.error

        return run_fit

    for types, tag in [(d.TYPES_4, "4types"), (d.TYPES_10, "10types")]:
        t_two = _time(_fit_fn("kernels", types), v)
        t_fused = _time(_fit_fn("fused", types), v)
        rows.append(Row(f"kernel/fit_twopass_{tag}", t_two * 1e6, f"P={p} n={n}"))
        rows.append(Row(
            f"kernel/fit_fused_{tag}", t_fused * 1e6,
            f"speedup={t_two / max(t_fused, 1e-9):.2f}x vs two-pass",
        ))

    # Select backends (DESIGN.md §6): device-side grouped dispatch
    # (quantize -> group_device -> gather -> fused fit -> scatter, one
    # launch + a scalar sync) vs the host Select path (np.unique bounce +
    # padded representative re-dispatch). Heavily-duplicated window so the
    # Select machinery, not the representative fit, dominates the row.
    from repro.core.executor import PDFConfig, StagedExecutor

    sp, sn, sg = (2048, 400, 48) if quick else (8192, 1000, 96)
    srng = np.random.default_rng(7)
    base = srng.normal(3000, 10, (sg, sn)).astype(np.float32)
    sel_np = base[srng.integers(0, sg, size=sp)]  # sp rows over sg distinct
    sel_times = {}
    for types, tag in [(d.TYPES_4, "4types"), (d.TYPES_10, "10types")]:
        for backend in ("host", "device"):
            cfg = PDFConfig(types=types, method="grouping",
                            select_backend=backend, rep_bucket=64)
            ex = StagedExecutor(cfg, None)
            win = Window(0, 0, 1)  # only feeds the sampling method's seed
            m = d.Moments(
                *jax.block_until_ready(ex._moments(jnp.asarray(sel_np)))
            )
            # fresh staged buffer per call: the device path donates the
            # window (as the executor does); staging cost is symmetric.
            ex._select_and_fit(jnp.asarray(sel_np), m, win)  # warmup/compile
            samples = []
            for _ in range(7):
                sv = jax.block_until_ready(jnp.asarray(sel_np))
                t0 = time.perf_counter()
                ex._select_and_fit(sv, m, win)  # returns np arrays (synchronous)
                samples.append(time.perf_counter() - t0)
            sel_times[(tag, backend)] = min(samples)
        t_host, t_dev = sel_times[(tag, "host")], sel_times[(tag, "device")]
        rows.append(Row(f"kernel/select_host_{tag}", t_host * 1e6,
                        f"P={sp} n={sn} G={sg} np.unique+re-dispatch"))
        rows.append(Row(
            f"kernel/select_device_{tag}", t_dev * 1e6,
            f"speedup={t_host / max(t_dev, 1e-9):.2f}x vs host Select",
        ))

    # banded attention kernel vs jnp band path (interpret mode on CPU)
    from repro.kernels.band_attn import banded_attention, banded_attention_ref
    b, s, h, kv, hd, w = (2, 256, 4, 2, 64, 64) if quick else (4, 2048, 8, 2, 128, 512)
    import jax as _jax
    q = _jax.random.normal(_jax.random.PRNGKey(1), (b, s, h, hd)) * 0.5
    kk = _jax.random.normal(_jax.random.PRNGKey(2), (b, s, kv, hd)) * 0.5
    vv = _jax.random.normal(_jax.random.PRNGKey(3), (b, s, kv, hd))
    t_ref = _time(jax.jit(lambda a, c, d: banded_attention_ref(a, c, d, w)), q, kk, vv)
    t_ker = _time(lambda a, c, d: banded_attention(a, c, d, w), q, kk, vv)
    rows.append(Row("kernel/band_attn_ref_jnp", t_ref * 1e6, f"S={s} W={w}"))
    rows.append(Row("kernel/band_attn_pallas_interpret", t_ker * 1e6,
                    "VMEM-resident scores; correctness: tests/test_band_attn_kernel.py"))
    sc_bytes = 2 * w * w * 4
    rows.append(Row("kernel/band_attn_vmem_scores", 0.0,
                    f"{sc_bytes/2**10:.0f}KiB scores tile (W={w}) stays in VMEM; "
                    f"{2*1024*1024*4/2**20:.0f}MiB at W=1024"))

    for bp, bn in [(8, 512), (8, 1024), (16, 512)]:
        b = vmem_bytes(bp, bn)
        rows.append(
            Row(f"kernel/vmem_block_{bp}x{bn}", 0.0,
                f"{b/1024:.0f}KiB of 16MiB VMEM ({'ok' if b < 16 * 2**20 else 'OVER'})")
        )
    # Fused fit kernel's TPU tile (one-hot accumulation path, 10 types):
    # values + freq scratch + the (T, bp, L) masses + the strip-mined one-hot.
    bp, bn, L, T = 8, 512, 64, 10
    fb = bp * bn * 4 + bp * L * 4 + T * bp * L * 4 + bp * bn * L * 4 // 16
    rows.append(
        Row(f"kernel/vmem_fitpdf_{bp}x{bn}", 0.0,
            f"{fb/1024:.0f}KiB of 16MiB VMEM ({'ok' if fb < 16 * 2**20 else 'OVER'})")
    )
    return rows
