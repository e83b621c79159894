from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.mesh import (  # noqa: F401
    MeshConfig,
    build_mesh,
    AXIS_DATA,
    AXIS_DCN,
    AXIS_EXPERT,
    AXIS_FSDP,
    AXIS_TENSOR,
    AXIS_SEQ,
    data_axis_names,
    current_mesh,
    maybe_current_mesh,
    use_mesh,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.ring_attention import (  # noqa: F401
    ring_attention,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.sharding import (  # noqa: F401
    batch_column_sharding,
    batch_sharding,
    named_sharding,
    param_shardings,
    replicated,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.distributed import (  # noqa: F401
    NoAcceleratorError,
    compilation_cache_dir,
    device_memory_peaks,
    enable_compilation_cache,
    initialize_distributed,
    require_accelerator,
)
