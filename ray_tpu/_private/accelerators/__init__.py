from ray_tpu._private.accelerators.tpu import (TPUAcceleratorManager,
                                               apply_chip_grant,
                                               detect_num_tpu_chips)

__all__ = ["TPUAcceleratorManager", "apply_chip_grant",
           "detect_num_tpu_chips"]
