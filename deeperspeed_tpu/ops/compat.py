"""Op availability registry — the TPU analogue of `op_builder/`
(reference: `op_builder/builder.py:81`, per-op `is_compatible()`).

The reference JIT-compiles CUDA extensions at first use; every Pallas
kernel here is compiled by XLA on first call, so "availability" is a
capability probe (backend, shape constraints), not a build step. `ds_report`
prints this matrix (reference `env_report.py:23`).
"""


def fused_adam_available():
    from .adam.fused_adam import FusedAdam  # noqa: F401
    return True


def cpu_adam_available():
    from .adam.fused_adam import DeepSpeedCPUAdam  # noqa: F401
    return True


def fused_lamb_available():
    from .lamb.fused_lamb import FusedLamb  # noqa: F401
    return True


def transformer_available():
    from .transformer import DeepSpeedTransformerLayer  # noqa: F401
    return True


def stochastic_transformer_available():
    # stochastic_mode is accepted by DeepSpeedTransformerConfig; bf16
    # compute supersedes the CUDA stochastic rounding mode.
    return transformer_available()


def flash_attention_available():
    from .pallas.flash_attention import flash_attention  # noqa: F401
    return True


def quant_matmul_available():
    # int8 weight-only matmul (per-channel scales, dequant-in-kernel)
    # for the serving decode/prefill weight path + the delayed-scaling
    # fp8/int8 training matmuls (docs/quantization.md)
    from .pallas.quant_matmul import quant_matmul  # noqa: F401
    return True


def int8_kv_decode_available():
    # the dequant-at-DMA int8 decode-attention variant. Probing the
    # KERNEL module only — importing inference.kv_cache would execute
    # the whole serving package __init__, and an unrelated serving-stack
    # import failure would misreport THIS op as unavailable
    from .pallas.decode_attention import paged_decode_attention  # noqa: F401
    return True


def sparse_attn_available():
    from .sparse_attention import SparseSelfAttention  # noqa: F401
    return True


def async_io_available():
    from ..runtime.swap_tensor.aio_engine import AsyncIOEngine
    return AsyncIOEngine.available()


def utils_available():
    # flatten/unflatten is native jnp (ravel/concatenate); always present.
    return True


def _builder_checks():
    """One registry: the op_builder builders are the source of truth
    (`ds_report` renders this dict); flash_attention is a kernel-level
    probe with no reference builder, so it is appended here."""
    from .op_builder import ALL_OPS as BUILDERS
    checks = {name: builder.is_compatible
              for name, builder in BUILDERS.items()}
    # keep flash_attention between the transformer and sparse_attn rows;
    # the quant kernel backends follow it (docs/quantization.md)
    ordered = {}
    for name in checks:
        ordered[name] = checks[name]
        if name == "stochastic_transformer":
            ordered["flash_attention"] = flash_attention_available
            ordered["quant_matmul"] = quant_matmul_available
            ordered["int8_kv_decode"] = int8_kv_decode_available
    return ordered


ALL_OPS = _builder_checks()
