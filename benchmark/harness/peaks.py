"""The table of peaks and the counts of operations and bytes.

Peaks — Google Cloud documentation, "TPU v5e" (system architecture): one
chip has 197 TFLOP/s in bfloat16, 16 GB of HBM at 819 GB/s.  JAX reports a
v5e chip as ``device_kind`` "TPU v5 lite".  A kind that is not in the table
is an error, never a default.  (Copied from
``distkeras_tpu/platform.py::DEVICE_PEAKS`` so that no later PR to the
program can move the yardstick.)

Counts — the operations the forward and backward passes REQUIRE, from the
shapes alone; recomputation is never counted.
"""

from __future__ import annotations

from typing import Any, Dict

DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def device_peaks(device_kind: str) -> Dict[str, float]:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device_kind {device_kind!r}; "
                         f"known: {sorted(DEVICE_PEAKS)}") from None


def train_flops_per_token(matmul_params: int, n_layer: int, seq_len: int,
                          d_model: int) -> Dict[str, float]:
    """Forward + backward FLOPs a trained token requires (the arithmetic of
    ``bench._bench_lm``, per token): 6 per matmul parameter, and causal
    attention 6 * n_layer * seq_len * d_model (the full QK^T and PV products
    are 12 * L * S * E a token; the causal half is needed)."""
    dense = 6.0 * matmul_params
    attention = 6.0 * n_layer * seq_len * d_model
    return {"dense": dense, "attention": attention, "total": dense + attention}


def flash_counts(direction: str, batch: int, heads: int, seq_len: int,
                 head_dim: int, bytes_per_el: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes ONE causal flash-attention call requires.

    Forward: QK^T and PV, 2 matmuls of 2*L*L*D FLOPs per head, half of them
    under the causal mask; it reads q, k, v and writes o.  Backward: five
    matmuls (recomputed scores do not count as required work: dv, dp, dq,
    dk and the score product the gradients need = 2.5x the forward's
    required FLOPs by the usual convention of counting S once); it reads q,
    k, v, o, do and writes dq, dk, dv.  Log-sum-exp rows are 4 bytes a
    position and left out (under 1%)."""
    bh = batch * heads
    fwd = 0.5 * 2 * 2.0 * seq_len * seq_len * head_dim * bh
    tensor = bh * seq_len * head_dim * bytes_per_el
    if direction == "fwd":
        return {"flops": fwd, "bytes": 4.0 * tensor}
    if direction == "bwd":
        return {"flops": 2.5 * fwd, "bytes": 8.0 * tensor}
    raise ValueError(f"direction must be 'fwd' or 'bwd', got {direction!r}")


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: Dict[str, float]) -> Dict[str, Any]:
    """The least time the chip could take over the time it took, in %, and
    which bound applies."""
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return {"share": 100.0 * max(t_flops, t_bytes) / seconds, "bound": bound}
