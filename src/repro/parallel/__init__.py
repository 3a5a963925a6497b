"""Distribution of the FFT pipelines: mesh axes and partition specs."""
from .fft_sharding import (fft_mesh_axis, infer_fft_mesh, pencil_specs,
                           shard_signals, data_mesh_axis, abft_group_layout,
                           abft_group_spec, slab_specs, pencil_nd_specs,
                           shard_grid)

__all__ = ["fft_mesh_axis", "infer_fft_mesh", "pencil_specs",
           "shard_signals", "data_mesh_axis", "abft_group_layout",
           "abft_group_spec", "slab_specs", "pencil_nd_specs", "shard_grid"]
