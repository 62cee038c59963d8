from repro.roofline.hw import TPU_V5E
from repro.roofline.analysis import (
    collective_bytes_from_hlo, roofline_terms, model_flops,
)
from repro.roofline.vmem import (
    TARGET_DEVICE_KIND, VMEM_BYTES, check_episode_vmem_fit,
    episode_vmem_plan, suggest_max_capacity, vmem_budget,
)

__all__ = ["TPU_V5E", "collective_bytes_from_hlo", "roofline_terms",
           "model_flops", "TARGET_DEVICE_KIND", "VMEM_BYTES",
           "check_episode_vmem_fit", "episode_vmem_plan",
           "suggest_max_capacity", "vmem_budget"]
