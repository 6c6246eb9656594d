from repro.configs.base import (  # noqa: F401
    AudioConfig,
    ModelConfig,
    MoEConfig,
    RopeScaling,
    SSMConfig,
    VisionConfig,
    get_config,
    register,
    registry,
    split_kind,
)
