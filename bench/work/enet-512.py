"""The convolution layers of ENet-512 (19 classes), the benchmark's own
copy: 4.121 GFLOP per frame (dense 76%, dilated 14.7%, transposed 9.3%).
Every one runs in a Pallas kernel on the timed path."""

from __future__ import annotations

from bench.work.layers import Layer

HW, CLASSES = 512, 19
#: kinds whose forward runs in a Pallas kernel
PALLAS_KINDS = ("dense", "dilated", "transposed")
STAGE2 = ((0, False), (2, False), (0, True), (4, False),
          (0, False), (8, False), (0, True), (16, False))


def _regular(p, hw, c, d=0, asym=False):
    ci = c // 4
    out = [Layer(f"{p}.reduce", "dense", hw, hw, hw, hw, c, ci)]
    if asym:
        out += [Layer(f"{p}.conv5x1", "dense", hw, hw, hw, hw, ci, ci, 5, 1),
                Layer(f"{p}.conv1x5", "dense", hw, hw, hw, hw, ci, ci, 1, 5)]
    else:
        kind = "dilated" if d else "dense"
        out.append(Layer(f"{p}.conv3x3", kind, hw, hw, hw, hw, ci, ci, 3, 3))
    out.append(Layer(f"{p}.expand", "dense", hw, hw, hw, hw, ci, c))
    return out


def _down(p, hw_out, cin, cout):
    ci = cout // 4
    h_in = 2 * hw_out
    return [Layer(f"{p}.reduce2x2s2", "dense", h_in, h_in, hw_out, hw_out,
                  cin, ci, 2, 2),
            Layer(f"{p}.conv3x3", "dense", hw_out, hw_out, hw_out, hw_out,
                  ci, ci, 3, 3),
            Layer(f"{p}.expand", "dense", hw_out, hw_out, hw_out, hw_out,
                  ci, cout)]


def _up(p, hw_out, cin, cout):
    ci, h = cout // 4, hw_out // 2
    return [Layer(f"{p}.reduce", "dense", h, h, h, h, cin, ci),
            Layer(f"{p}.deconv3x3s2", "transposed", h, h, hw_out, hw_out,
                  ci, ci, 3, 3),
            Layer(f"{p}.expand", "dense", hw_out, hw_out, hw_out, hw_out,
                  ci, cout),
            Layer(f"{p}.skip1x1", "dense", h, h, h, h, cin, cout)]


def layers(classes: int = CLASSES) -> list[Layer]:
    L = [Layer("initial", "dense", HW, HW, HW // 2, HW // 2, 3, 13, 3, 3)]
    L += _down("b1.0", 128, 16, 64)
    for i in range(1, 5):
        L += _regular(f"b1.{i}", 128, 64)
    L += _down("b2.0", 64, 64, 128)
    for stage in (2, 3):
        for i, (d, asym) in enumerate(STAGE2, start=1):
            L += _regular(f"b{stage}.{i}", 64, 128, d, asym)
    L += _up("b4.0", 128, 128, 64)
    for i in range(1, 3):
        L += _regular(f"b4.{i}", 128, 64)
    L += _up("b5.0", 256, 64, 16)
    L += _regular("b5.1", 256, 16)
    L.append(Layer("fullconv", "transposed", 256, 256, HW, HW, 16, classes,
                   3, 3))
    return L
