from dgraph_tpu.ops import local

# dgraph_tpu.ops.pallas_segment is imported lazily by its dispatch point
# (ops.local) so importing the package never pays the Pallas import on
# paths that don't run kernels.
__all__ = ["local"]
