"""Hardware profiles for the analytic latency model.

The paper calibrates its simulator against NVIDIA A100 operator profiles; we
additionally provide the TPU v5e profile used by the roofline analysis so the
simulator and the dry-run share constants. ``mfu`` is the sustained fraction
of peak compute the latency model assumes for dense prefill operators (Vidur
profiles encode the same information empirically).
"""
from __future__ import annotations

from dataclasses import dataclass

__all__ = ["HW", "A100", "TPU_V5E", "RTX3090", "DEVICE_HW", "hw_for_device"]

GB = 1e9
Gb = 1e9 / 8


@dataclass(frozen=True)
class HW:
    name: str
    flops: float          # peak matmul FLOP/s (bf16)
    hbm_bw: float         # bytes/s
    nic_bw: float         # bytes/s per endpoint (network share per GPU)
    scaleup_bw: float     # bytes/s intra-server fabric per endpoint
    mfu: float = 0.45     # sustained fraction of peak for prefill GEMMs
    hbm_eff: float = 0.75


# Simulation default (§6.1: latency profiles calibrated on A100; 8 NICs per
# 8-GPU server at 200 Gbps; NVSwitch 900 GB/s).
A100 = HW("a100", flops=312e12, hbm_bw=2039 * GB, nic_bw=200 * Gb,
          scaleup_bw=900 * GB)

# Testbed (§6.1): RTX 3090 + 2x100G NICs shared by 4 GPUs => 50 Gbps/GPU,
# PCIe Gen3 x16 intra-server (~16 GB/s).
RTX3090 = HW("rtx3090", flops=71e12, hbm_bw=936 * GB, nic_bw=50 * Gb,
             scaleup_bw=16 * GB, mfu=0.35)

# TPU v5e, one chip. flops and hbm_bw are the published peaks (Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s).
# nic_bw / scaleup_bw take one of the chip's four ICI links (1,600 Gbit/s
# in all, same source); mfu is this model's assumption, not a measurement.
TPU_V5E = HW("tpu_v5e", flops=197e12, hbm_bw=819 * GB, nic_bw=50 * GB,
             scaleup_bw=50 * GB, mfu=0.5)

#: Peak table for runs on a chip, keyed by ``jax.Device.device_kind``.
DEVICE_HW = {"TPU v5 lite": TPU_V5E}


def hw_for_device(device_kind: str) -> HW:
    """The peak table of the chip a run is on. A kind missing from
    ``DEVICE_HW`` is an error: pass ``HW`` explicitly to model another
    target (as CPU runs must)."""
    try:
        return DEVICE_HW[device_kind]
    except KeyError:
        raise KeyError(f"no peak table for device kind {device_kind!r} "
                       f"(known: {sorted(DEVICE_HW)}); pass hw explicitly"
                       ) from None
