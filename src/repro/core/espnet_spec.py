"""ESPNet @ 1024x512 per-layer workload table (second accelerator workload).

Mirrors :mod:`repro.models.espnet` (the published ESPNet, alpha2 = 2,
alpha3 = 8, K = 5 pyramid branches at d = 1, 2, 4, 8, 16) the same way
:mod:`repro.core.enet_spec` mirrors :mod:`repro.models.enet` — each entry
records the convolution workload only, on Cityscapes frames of 1024x512
(W x H).

Differences from the ENet table that matter to the cycle model:

* dilation rates run the whole band in every module (D = 1, 3, 7, 15 side by
  side, vs ENet's one rate per bottleneck), on narrow branches (n = 12 at
  level 2, 25 at level 3);
* every dilated branch is stride 1: a DownSamplerB reduces with a 3x3
  stride-2 dense conv first, so the strided output-class schedule is not
  exercised;
* the decoder's three upsamplers are 2x2 stride-2 transposed convs (each
  parity plane is a single tap), on ``num_classes`` channels.
"""

from __future__ import annotations

from repro.core.enet_spec import ConvLayer

ESP_DILATIONS = (1, 2, 4, 8, 16)
H, W = 512, 1024


def esp_module_layers(prefix: str, h: int, w: int, cin: int, cout: int,
                      down: bool = False) -> list[ConvLayer]:
    """ESP module at an ``h x w`` output: the reduce (1x1, or 3x3 stride 2
    for a DownSamplerB) and K parallel stride-1 3x3 branches.

    The d = 1 branch is a plain dense conv (group "general"); d > 1 branches
    are dilated convs (group "dilated").
    """
    n = cout // len(ESP_DILATIONS)
    n1 = cout - (len(ESP_DILATIONS) - 1) * n
    if down:
        layers = [ConvLayer(f"{prefix}.reduce3x3s2", "conv", h, w, cin, n,
                            3, 3, stride=2)]
    else:
        layers = [ConvLayer(f"{prefix}.reduce", "conv", h, w, cin, n, 1, 1)]
    for d in ESP_DILATIONS:
        if d == 1:
            layers.append(ConvLayer(f"{prefix}.br_d1", "conv", h, w, n, n1,
                                    3, 3))
        else:
            layers.append(ConvLayer(f"{prefix}.br_d{d}", "dilated", h, w, n, n,
                                    3, 3, D=d - 1, group="dilated"))
    return layers


def espnet_layers(num_classes: int = 20, alpha2: int = 2,
                  alpha3: int = 8) -> list[ConvLayer]:
    c = num_classes
    h2, w2 = H // 2, W // 2
    h4, w4 = H // 4, W // 4
    h8, w8 = H // 8, W // 8

    def up(name, h, w):
        return ConvLayer(name, "transposed", h, w, c, c, 2, 2, stride=2,
                         group="transposed", output_padding=0, padding=1)

    L = [ConvLayer("level1", "conv", h2, w2, 3, 16, 3, 3, stride=2)]
    L += esp_module_layers("l2.0", h4, w4, 19, 64, down=True)
    for i in range(1, alpha2 + 1):
        L += esp_module_layers(f"l2.{i}", h4, w4, 64, 64)
    L += esp_module_layers("l3.0", h8, w8, 131, 128, down=True)
    for i in range(1, alpha3 + 1):
        L += esp_module_layers(f"l3.{i}", h8, w8, 128, 128)
    L.append(ConvLayer("cls3", "conv", h8, w8, 256, c, 1, 1))
    L.append(up("up3", h4, w4))
    L.append(ConvLayer("cls2", "conv", h4, w4, 131, c, 1, 1))
    L += esp_module_layers("comb", h4, w4, 2 * c, c)
    L.append(up("up2", h2, w2))
    L.append(ConvLayer("fuse", "conv", h2, w2, 19 + c, c, 3, 3))
    L.append(up("up1", H, W))
    return L
