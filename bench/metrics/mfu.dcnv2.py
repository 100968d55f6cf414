"""Model FLOPs of the DLRM-DCNv2 forward pass per sample
(``flops_dcnv2.dcnv2_forward_flops``) times the samples scored per second,
over the chip's bf16 peak, in %."""

from bench.flops_dcnv2 import dcnv2_forward_flops
from bench.peaks import device_kind, peak


def read(run):
    if not run.record.get("elapsed_s"):
        return None
    rate = run.record["samples"] / run.record["elapsed_s"]
    return (dcnv2_forward_flops(run.config) * rate
            / peak(device_kind(), "bf16_flops_per_s") * 100)
