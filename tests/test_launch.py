"""Launch-layer tests that need no fake-device mesh: input specs, presets,
applicability, report rendering, benchlib plumbing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES, shape_applicable
from repro.launch.presets import preset_for
from repro.launch.report import _diagnosis, dryrun_table, roofline_table
from repro.launch.specs import input_specs
from repro.launch.roofline import analyze, model_flops_for_cell, peaks_for


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_input_specs_shapes(arch, shape):
    cfg = get_config(arch)
    spec = input_specs(arch, shape)
    ss = SHAPES[shape]
    if ss.kind == "train":
        assert spec["labels"].shape == (ss.global_batch, ss.seq_len)
    lead = spec.get("tokens", spec.get("embeddings"))
    if ss.kind == "decode":
        assert lead.shape[1] == 1
    else:
        assert lead.shape[:2] == (ss.global_batch, ss.seq_len)
    if cfg.frontend == "embeddings":
        assert "tokens" not in spec
        assert spec["embeddings"].shape[-1] == cfg.d_model
    # no device allocation: everything is ShapeDtypeStruct
    for v in spec.values():
        assert isinstance(v, jax.ShapeDtypeStruct)


def test_all_presets_resolve():
    for arch in ARCH_IDS:
        p = preset_for(arch)
        assert p.microbatches >= 1
        ss = SHAPES["train_4k"]
        assert ss.global_batch % p.microbatches == 0


def test_applicability_matrix():
    live = 0
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for name, ss in SHAPES.items():
            ok, why = shape_applicable(cfg, ss)
            if ok:
                live += 1
            else:
                assert name == "long_500k" and not cfg.subquadratic
                assert "full-attention" in why
    assert live == 32  # 10×3 + 2 long_500k


def test_analyze_bottleneck_selection():
    cfg = get_config("qwen3-14b")
    ss = SHAPES["train_4k"]
    hlo = "ENTRY %main (p: f32[4]) -> f32[4] {\n  ROOT %r = f32[4] copy(%p)\n}"
    rep = analyze("qwen3-14b", ss, "single", 256,
                  {"flops": 1e12, "bytes accessed": 1e9}, {}, hlo, cfg,
                  {"flops": 1e18, "bytes": 1e12, "bytes_ub": 1e13},
                  device_kind="TPU v5 lite")
    assert rep.bottleneck == "compute"
    assert rep.compute_s == pytest.approx(
        1e18 / (256 * peaks_for("TPU v5 lite").peak_flops))
    assert 0 < rep.useful_ratio < 1
    assert rep.peak_fraction <= 1.0


def test_peaks_unknown_device_kind_raises():
    assert peaks_for("TPU v5 lite").hbm_bw == 819e9
    with pytest.raises(KeyError, match="cpu"):
        peaks_for("cpu")


def test_model_flops_decode_scaling():
    cfg = get_config("qwen3-14b")
    d = model_flops_for_cell(cfg, SHAPES["decode_32k"])
    t = model_flops_for_cell(cfg, SHAPES["train_4k"])
    # decode: 2·N per generated token × 128; train: 6·N × 1M tokens
    assert t / d == pytest.approx(3 * 4096 * 256 / 128)


def test_report_renders_rows():
    rows = [{"arch": "a", "shape": "train_4k", "mesh": "single",
             "status": "skipped", "reason": "x" * 100},
            {"arch": "b", "shape": "decode_32k", "mesh": "single",
             "status": "ok",
             "roofline": {
                 "compute_s": 1.0, "memory_s": 2.0, "collective_s": 0.5,
                 "bottleneck": "memory", "useful_ratio": 0.8,
                 "peak_fraction": 0.3, "notes": "",
                 "memory_stats": {"temp_size_in_bytes": 2**30,
                                  "argument_size_in_bytes": 2**29},
                 "collectives": {"all-reduce": {"count": 3, "bytes": 1,
                                                "wire_bytes": 2}}}}]
    dt = dryrun_table(rows)
    rt = roofline_table(rows)
    assert "SKIP" in dt and "| b |" in dt
    assert "memory-bound" in rt


def test_diagnosis_strings():
    base = {"useful_ratio": 0.8, "bottleneck": "compute"}
    assert "near-roofline" in _diagnosis(base)
    assert "remat" in _diagnosis({**base, "useful_ratio": 0.3})
    assert "flash" in _diagnosis({**base, "bottleneck": "memory"})
    assert "collective" in _diagnosis({**base, "bottleneck": "collective"})


def test_benchlib_bucketing_and_cache():
    from repro import benchlib
    from repro.core.suite import generate, SUITE
    spec = next(s for s in SUITE if s.name.startswith("blkdiag_1024"))
    a = generate(spec)
    r1 = benchlib.bench_rowwise_on(a, "original", name="t_" + spec.name,
                                   reps=1)
    r2 = benchlib.bench_rowwise_on(a, "original", name="t_" + spec.name,
                                   reps=1)
    assert r1.kernel_s == r2.kernel_s      # cached
    assert r1.flops > 0 and r1.nnz == a.nnz


def test_representative_subset_stratified():
    from repro.benchlib import representative_subset
    subset = representative_subset(18)
    fams = {s.family for s in subset}
    assert len(subset) == 18
    assert len(fams) >= 8          # every family present
    assert sum(s.scrambled for s in subset) >= 8
