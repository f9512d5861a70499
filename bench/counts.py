"""Operations and bytes the sparse FFN needs, from its widths alone.

The counts read the pruned network's nonzero blocks, never the plan's
scheduled steps or the kernel's grid, so a change to either cannot move
the yardstick.  For one kernel call of ``rows`` rows:

  * FLOPs = 2 * rows * nnz * block^2;
  * bytes = nnz * block^2 * (bytes per weight element)   weight blocks
          + nnz * 4                                      dequant scales
          + rows * (n_in + n_out) * 4                    input and output
          + (n_hid + n_out) * 4                          biases
"""

from __future__ import annotations

import dataclasses

WEIGHT_BYTES = {"f32": 4, "bf16": 2, "fp8": 1}


@dataclasses.dataclass(frozen=True)
class SparseFFN:
    n_in: int
    n_hid: int
    n_out: int
    block: int
    nnz: int                 # nonzero blocks of the pruned network
    weight_dtype: str

    def flops(self, rows: int) -> int:
        return 2 * rows * self.nnz * self.block ** 2

    def bytes(self, rows: int) -> int:
        return (self.nnz * self.block ** 2 * WEIGHT_BYTES[self.weight_dtype]
                + self.nnz * 4
                + rows * (self.n_in + self.n_out) * 4
                + (self.n_hid + self.n_out) * 4)

    def flops_per_row(self) -> int:
        """Useful operations per served row, for ``mfu``."""
        return 2 * self.nnz * self.block ** 2

    def bound_s(self, rows: int, peak: dict) -> float:
        """Least time one call can take on a chip with ``peak``: the larger
        of operations over peak FLOP/s and bytes over peak bytes/s."""
        return max(self.flops(rows) / peak["flops_per_s"],
                   self.bytes(rows) / peak["bytes_per_s"])

    def bound_by(self, rows: int, peak: dict) -> str:
        """``"compute"`` or ``"memory"``: which side binds ``bound_s``."""
        t_flops = self.flops(rows) / peak["flops_per_s"]
        t_bytes = self.bytes(rows) / peak["bytes_per_s"]
        return "compute" if t_flops >= t_bytes else "memory"
