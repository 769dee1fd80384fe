"""Architecture registry. Importing this package registers all assigned
architectures plus the paper's own LSTM workload config."""
from repro.configs.base import (  # noqa: F401
    SHAPES,
    ArchConfig,
    MLAConfig,
    MoEConfig,
    SSMConfig,
    get_config,
    get_reduced_config,
    input_specs,
    list_archs,
    register,
)

# Register every assigned architecture (one module each).
from repro.configs import (  # noqa: F401
    deepseek_v3_671b,
    granite_3_8b,
    granite_4_0_h_small,
    granite_34b,
    granite_moe_3b_a800m,
    internvl2_76b,
    mamba2_780m,
    qwen15_110b,
    starcoder2_15b,
    whisper_tiny,
    zamba2_7b,
)
