from .kernel import (qadd_pallas, qconv1x1_pallas, qconv_pallas,
                     qdwconv_pallas)
from .ops import qadd_fused, qconv_fused, qdwconv_fused

__all__ = ["qadd_pallas", "qconv1x1_pallas", "qconv_pallas",
           "qdwconv_pallas", "qadd_fused", "qconv_fused", "qdwconv_fused"]
