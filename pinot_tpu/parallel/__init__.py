from pinot_tpu.parallel.multichip import default_mesh, make_sharded_table_kernel

__all__ = ["default_mesh", "make_sharded_table_kernel"]
