"""PVT good fixture: every sanctioned shape of private-jax use — a
try/except-ImportError-gated import (graceful degradation, jax_compat
style), the inline inspect.signature pin, and the
utils.private_api.pin_signature helper idiom, on a function and on a
dataclass (whose signature is its fields). All pins match the
installed jax 0.9.0, so the file stays silent."""

import inspect

from areal_tpu.utils.private_api import pin_signature

try:  # gated: degrades gracefully when the private layout moves
    from jax._src.core import get_axis_env
except ImportError:
    get_axis_env = None

# inline pin idiom, matching the installed jax 0.9.0 signature
from jax.experimental.pallas.ops.tpu.paged_attention.paged_attention_kernel import (
    paged_flash_attention_kernel_inline_seq_dim as _kernel,
)

_EXPECTED_KERNEL_PARAMS = (
    "lengths_ref",
    "page_indices_ref",
    "buffer_index_ref",
    "init_flag_ref",
    "q_ref",
    "k_pages_hbm_ref",
    "k_scales_pages_hbm_ref",
    "v_pages_hbm_ref",
    "v_scales_pages_hbm_ref",
    "o_ref",
    "m_ref",
    "l_ref",
    "k_vmem_buffer",
    "k_scales_vmem_buffer",
    "v_vmem_buffer",
    "v_scales_vmem_buffer",
    "k_sems",
    "v_sems",
    "batch_size",
    "pages_per_compute_block",
    "pages_per_sequence",
    "mask_value",
    "attn_logits_soft_cap",
    "megacore_mode",
)
if tuple(inspect.signature(_kernel).parameters) != _EXPECTED_KERNEL_PARAMS:
    raise ImportError("re-audit the launch fork against the new kernel")

# helper idiom
from jax.experimental.pallas.ops.tpu.megablox import gmm

_EXPECTED_GMM_PARAMS = (
    "lhs",
    "rhs",
    "group_sizes",
    "preferred_element_type",
    "tiling",
    "group_offset",
    "existing_out",
    "transpose_rhs",
    "interpret",
)
pin_signature(gmm, _EXPECTED_GMM_PARAMS)

# the helper idiom on a private dataclass: every field a call site fills
from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

_EXPECTED_BLOCK_SIZES_FIELDS = (
    "block_q",
    "block_k_major",
    "block_k",
    "block_b",
    "block_q_major_dkv",
    "block_k_major_dkv",
    "block_k_dkv",
    "block_q_dkv",
    "block_k_major_dq",
    "block_k_dq",
    "block_q_dq",
)
pin_signature(BlockSizes, _EXPECTED_BLOCK_SIZES_FIELDS)
