"""How the benchmark calls the system under test for DCGAN: the
repository's generative server (``GenServer``) with one DCGAN lane on the
Pallas engines (compiled on a TPU).  The weights come from the benchmark
(``bench/refs/dcgan.py``)."""

from __future__ import annotations


def lane(cfg: dict) -> str:
    return f"dcgan{cfg['image_size']}"


def server(cfg: dict, params: dict):
    from repro.launch.serve_gen import GenServer

    return GenServer(batch=cfg["lane_batch"], backend="pallas",
                     interpret=None, dcgan_nz=cfg["nz"],
                     dcgan_ngf=cfg["ngf"], out_ch=cfg["nc"],
                     autoscale=False, params={lane(cfg): params})
