from repro.kernels.gather.boundary import boundary_gather, boundary_gather_xla
from repro.kernels.gather.paged import (paged_gather, paged_gather_quant,
                                        paged_gather_quant_xla,
                                        paged_gather_xla)

__all__ = ["boundary_gather", "boundary_gather_xla", "paged_gather",
           "paged_gather_quant", "paged_gather_quant_xla", "paged_gather_xla"]
