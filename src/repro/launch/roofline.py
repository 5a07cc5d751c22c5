"""Roofline machinery for the dry-run.

XLA's cost analysis counts a lax.scan (while-loop) body ONCE regardless of
trip count, so scanned-layer models under-report FLOPs/collectives.  The fix:
compile small *unrolled* probe configs (force_unroll=True), express each probe
as a layer-kind composition vector, solve the linear model

    metric(config) = intercept + Σ_kind  n_kind · coeff_kind

by least squares, and predict the full config exactly (probe compositions are
chosen so the full-config vector lies in their span).  Memory analysis comes
from the full compile (layout/liveness are layer-count independent under
scan); FLOPs, bytes-accessed and collective bytes come from the probe model.

Roofline terms per (arch × shape) on the single-pod mesh (TPU v5e):
    compute_s    = HLO_FLOPs_per_chip   / 197e12
    memory_s     = HLO_bytes_per_chip   / 819e9
    collective_s = coll_bytes_per_chip  / 50e9
"""
from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Dict, List, Tuple

import numpy as np

from repro.launch import mesh as mesh_lib
from repro.models.config import InputShape, ModelConfig


def probe_layer_plans(cfg: ModelConfig) -> List[Dict[str, int]]:
    """Probe configs: {'num_layers': L, 'encoder_layers': E} overrides."""
    if cfg.is_encoder_decoder:
        return [{"num_layers": 1, "encoder_layers": 1},
                {"num_layers": 2, "encoder_layers": 1},
                {"num_layers": 1, "encoder_layers": 2}]
    if cfg.hybrid_period > 0:
        p = cfg.hybrid_period
        return [{"num_layers": 1}, {"num_layers": p}, {"num_layers": 2 * p}]
    if cfg.first_k_dense > 0:
        k = cfg.first_k_dense
        return [{"num_layers": k}, {"num_layers": k + 1}, {"num_layers": k + 2}]
    return [{"num_layers": 1}, {"num_layers": 2}]


def composition_vector(cfg: ModelConfig, keys: List[str]) -> np.ndarray:
    counts = Counter(f"{m}/{f}" for m, f in cfg.layer_kinds())
    counts["_intercept"] = 1
    counts["_encoder"] = cfg.encoder_layers if cfg.is_encoder_decoder else 0
    return np.array([float(counts.get(k, 0)) for k in keys])


def composition_keys(cfg: ModelConfig) -> List[str]:
    kinds = sorted(set(f"{m}/{f}" for m, f in cfg.layer_kinds()))
    keys = ["_intercept"] + kinds
    if cfg.is_encoder_decoder:
        keys.append("_encoder")
    return keys


def probe_configs(cfg: ModelConfig) -> List[ModelConfig]:
    out = []
    for plan in probe_layer_plans(cfg):
        # mtp (deepseek) stays on: it is layer-count-constant, so it lands in
        # the intercept and the prediction includes it exactly once.
        out.append(dataclasses.replace(cfg, force_unroll=True, **plan))
    return out


def extrapolate(cfg: ModelConfig, probe_cfgs: List[ModelConfig],
                probe_metrics: List[Dict[str, float]]) -> Dict[str, float]:
    """Least-squares solve + predict for every metric key."""
    keys = composition_keys(cfg)
    A = np.stack([composition_vector(c, keys) for c in probe_cfgs])
    target = composition_vector(cfg, keys)
    out = {}
    metric_names = probe_metrics[0].keys()
    for name in metric_names:
        y = np.array([m[name] for m in probe_metrics])
        coef, *_ = np.linalg.lstsq(A, y, rcond=None)
        out[name] = float(max(0.0, target @ coef))
    return out


def roofline_terms(per_chip_flops: float, per_chip_bytes: float,
                   per_chip_coll_bytes: float) -> Dict[str, float]:
    peaks = mesh_lib.chip_peaks(mesh_lib.V5E)
    compute_s = per_chip_flops / peaks.flops_bf16
    memory_s = per_chip_bytes / peaks.hbm_bw
    collective_s = per_chip_coll_bytes / peaks.ici_bw_per_link
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dom = max(terms, key=terms.get)
    terms["dominant"] = dom
    total = max(compute_s, memory_s, collective_s)
    terms["bound_s"] = total
    return terms
