from repro.kernels.grouped_matmul.ops import active_rows, gmm, row_tile

__all__ = ["active_rows", "gmm", "row_tile"]
