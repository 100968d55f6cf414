"""The pooled multi-hot lookup's share of its roofline, in %: the bytes
any pooled lookup of the batch has to move
(``flops_dcnv2.pooled_lookup_bytes``) at the chip's HBM peak, over the
device time of the program's own ``embedding`` scope per call
(``lookup_ms.dcnv2``).  Bytes bound it: its hashing is a few integer
operations per element, its pooling one add per gathered element."""

from bench.flops_dcnv2 import pooled_lookup_bytes
from bench.peaks import device_kind, peak
from bench.scopes import keep_scopes, scope_ms

warm = keep_scopes


def read(run):
    ms = scope_ms(run, ("embedding",), "calls")
    if not ms:
        return None
    need = pooled_lookup_bytes(run.config, run.traffic["batch"])
    return need / peak(device_kind(), "hbm_bytes_per_s") / (ms * 1e-3) * 100
