"""The convolution layers of ESPNet at 1024x512 (20 classes, alpha2 2,
alpha3 8), the benchmark's own copy: 85 convolutions, 9.56 GFLOP per frame
(dense 48.5%, dilated 45.8% in 52 branches at d = 2, 4, 8, 16, transposed
5.8%).  Every one runs in a Pallas kernel on the timed path."""

from __future__ import annotations

from bench.work.layers import Layer

H, W, CLASSES = 512, 1024, 20
ALPHA2, ALPHA3 = 2, 8
DILATIONS = (1, 2, 4, 8, 16)
#: kinds whose forward runs in a Pallas kernel
PALLAS_KINDS = ("dense", "dilated", "transposed")


def _esp(p, h, w, cin, cout, down=False):
    """Reduce (3x3 stride 2 from 2h x 2w where ``down``, else 1x1) and the
    five branches at h x w."""
    n = cout // 5
    n1 = cout - 4 * n
    if down:
        out = [Layer(f"{p}.reduce3x3s2", "dense", 2 * h, 2 * w, h, w, cin, n,
                     3, 3)]
    else:
        out = [Layer(f"{p}.reduce", "dense", h, w, h, w, cin, n)]
    for d in DILATIONS:
        out.append(Layer(f"{p}.d{d}", "dilated" if d > 1 else "dense",
                         h, w, h, w, n, n1 if d == 1 else n, 3, 3))
    return out


def layers(classes: int = CLASSES) -> list[Layer]:
    c = classes
    h2, w2, h4, w4, h8, w8 = H // 2, W // 2, H // 4, W // 4, H // 8, W // 8
    L = [Layer("level1", "dense", H, W, h2, w2, 3, 16, 3, 3)]
    L += _esp("l2.0", h4, w4, 19, 64, down=True)
    for i in range(1, ALPHA2 + 1):
        L += _esp(f"l2.{i}", h4, w4, 64, 64)
    L += _esp("l3.0", h8, w8, 131, 128, down=True)
    for i in range(1, ALPHA3 + 1):
        L += _esp(f"l3.{i}", h8, w8, 128, 128)
    L += [Layer("cls3", "dense", h8, w8, h8, w8, 256, c),
          Layer("up3", "transposed", h8, w8, h4, w4, c, c, 2, 2),
          Layer("cls2", "dense", h4, w4, h4, w4, 131, c)]
    L += _esp("comb", h4, w4, 2 * c, c)
    L += [Layer("up2", "transposed", h4, w4, h2, w2, c, c, 2, 2),
          Layer("fuse", "dense", h2, w2, h2, w2, 19 + c, c, 3, 3),
          Layer("up1", "transposed", h2, w2, H, W, c, c, 2, 2)]
    return L
