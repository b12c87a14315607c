from ray_tpu.ops.decode_attention import decode_attention
from ray_tpu.ops.expert_stream import experts_streamed
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.ops.linear_attention import linear_attention
from ray_tpu.ops.selective_scan import selective_scan, selective_scan_step
from ray_tpu.ops.sparse_attention import sparse_attention
from ray_tpu.ops.ssd import ssd_fwd, ssd_step, ssd_step_stacked

__all__ = ["decode_attention", "experts_streamed", "flash_attention",
           "linear_attention", "selective_scan", "selective_scan_step",
           "sparse_attention", "ssd_fwd", "ssd_step", "ssd_step_stacked"]
