"""The layers of the DCGAN-64 generator (nz=100, ngf=64), the
benchmark's own copy: 0.209 GFLOP per image, 99.2% of it transposed.
The projection is a matmul in XLA; the four k=4, s=2 transposed
convolutions run in Pallas kernels on the timed path."""

from __future__ import annotations

from bench.work.layers import Layer

#: kinds whose forward runs in a Pallas kernel
PALLAS_KINDS = ("transposed",)


def layers(nz: int = 100, ngf: int = 64, nc: int = 3) -> list[Layer]:
    c = ngf * 8
    L = [Layer("proj", "matmul", 1, 1, 4, 4, nz, c)]
    hw = 4
    while c > ngf:
        L.append(Layer(f"up{len(L)}", "transposed", hw, hw, 2 * hw, 2 * hw,
                       c, c // 2, 4, 4))
        hw, c = 2 * hw, c // 2
    L.append(Layer("head", "transposed", hw, hw, 2 * hw, 2 * hw, c, nc, 4, 4))
    return L
