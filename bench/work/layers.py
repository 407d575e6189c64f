"""Useful work of one convolution layer: FLOPs with no inserted zeros and
the least bytes it must move, the yardstick of ``conv_roofline`` and
``mfu``.  The tables in this directory describe each configuration's
layers, so the count reads the same whatever implements a layer.

* dense and dilated: ``2 * H_out * W_out * kh * kw * Cin * Cout``;
* transposed (stride s): every tap of every *input* pixel,
  ``2 * H_in * W_in * kh * kw * Cin * Cout`` (``H_in = H_out / s``);
* bytes: the input, the weights and the output, once each, in float32.
"""

from __future__ import annotations

from dataclasses import dataclass

F32 = 4


@dataclass(frozen=True)
class Layer:
    name: str
    kind: str            # dense | dilated | transposed | matmul
    h_in: int
    w_in: int
    h_out: int
    w_out: int
    cin: int
    cout: int
    kh: int = 1
    kw: int = 1

    def flops(self, batch: int) -> float:
        if self.kind == "transposed":
            pixels = self.h_in * self.w_in
        else:
            pixels = self.h_out * self.w_out
        return 2.0 * batch * pixels * self.kh * self.kw * self.cin * self.cout

    def bytes(self, batch: int) -> float:
        acts = batch * (self.h_in * self.w_in * self.cin
                        + self.h_out * self.w_out * self.cout)
        return F32 * (acts + self.kh * self.kw * self.cin * self.cout)

    def min_seconds(self, batch: int, peak: dict) -> float:
        """Least time on the chip: the larger of its two roofline bounds."""
        return max(self.flops(batch) / peak["bf16_flops"],
                   self.bytes(batch) / peak["hbm_bytes_per_s"])


def totals(layers, batch: int, peak: dict | None = None,
           kinds=None) -> dict:
    """Sums over ``layers`` (those of ``kinds`` if given)."""
    sel = [l for l in layers if kinds is None or l.kind in kinds]
    out = {"flops": sum(l.flops(batch) for l in sel),
           "bytes": sum(l.bytes(batch) for l in sel)}
    if peak is not None:
        out["min_seconds"] = sum(l.min_seconds(batch, peak) for l in sel)
    return out
