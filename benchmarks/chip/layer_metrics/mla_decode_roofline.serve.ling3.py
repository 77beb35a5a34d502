"""The fused paged kernel's share of its roofline on Ling 3.0 flash's latent
pool (absorbed latent attention, 32 heads against one shared row, one latent
layer a step): `mla_decode_roofline.serve`'s reading, whose cost function
(`flops_kimi_k2.mla_decode_cost`: the larger of the live latent bytes at the
HBM bandwidth and the absorbed form's FLOPs at the bf16 peak; 60 FLOP a byte at
32 heads puts the kernel under the bytes' roof) reads the keys both
configurations have, over the `%attn.N` events and the program's live rows. A
metric's reader is found by the metric's name, so this file hands on the
other's `read`. In percent."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "mla_decode_roofline_serve", os.path.join(os.path.dirname(__file__), "mla_decode_roofline.serve.py"))
_reader = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_reader)
read = _reader.read
