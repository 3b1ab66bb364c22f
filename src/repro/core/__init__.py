"""GOLDYLOC core: globally-optimized GEMM kernels + lightweight dynamic
concurrency control, adapted to TPU (see DESIGN.md)."""
from repro.core.cost_model import (
    CHIP_SPECS,
    DEFAULT_SPEC,
    RC_FRACTIONS,
    SLICE_OVERHEAD_S,
    CostCalibrator,
    TPUSpec,
    device_spec,
    group_time,
    isolated_time,
    kernel_stats,
    sequential_time,
    sliced_time,
    speedup_vs_sequential,
)
from repro.core.gemm_desc import GemmDesc, split_spans
from repro.core.library import GOLibrary, default_library
from repro.core.measure import (
    Measurement,
    Measurer,
    backend_tag,
)
from repro.core.op_desc import (
    FAMILIES,
    AttentionDesc,
    GroupedGemmDesc,
    ScanDesc,
    SlicePlan,
    family_of,
    op_from_key,
    slice_plan,
)
from repro.core.predictor import (
    CLASSES,
    Predictor,
    accuracy_by_available,
    gemm_features,
    generate_gemm_pool,
    op_features,
    profile_dataset,
    train_predictor,
)
from repro.core.scheduler import (
    CP_OVERHEAD_S,
    ConcurrencyController,
    GemmRequest,
    GroupPlan,
    Schedule,
    compat_key,
    execute_schedule,
)
from repro.core.tuner import (
    CDS,
    GOEntry,
    go_kernel_properties,
    tune_gemm,
    tune_gemm_batch,
    tune_op,
)

__all__ = [
    "CHIP_SPECS", "DEFAULT_SPEC", "RC_FRACTIONS", "TPUSpec", "device_spec",
    "group_time", "isolated_time",
    "kernel_stats", "sequential_time", "speedup_vs_sequential", "GemmDesc",
    "CostCalibrator", "Measurement", "Measurer", "backend_tag",
    "execute_schedule", "SLICE_OVERHEAD_S", "sliced_time", "split_spans",
    "SlicePlan", "slice_plan",
    "GOLibrary", "default_library", "FAMILIES", "AttentionDesc",
    "GroupedGemmDesc", "ScanDesc", "family_of", "op_from_key", "CLASSES",
    "Predictor", "accuracy_by_available", "gemm_features",
    "generate_gemm_pool", "op_features", "profile_dataset",
    "train_predictor", "CP_OVERHEAD_S", "ConcurrencyController",
    "GemmRequest", "GroupPlan", "Schedule", "compat_key", "CDS", "GOEntry",
    "go_kernel_properties", "tune_gemm", "tune_gemm_batch", "tune_op",
]
