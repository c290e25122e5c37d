"""Needed work of the test-only DDPM family. Its program serves the
same DiT as the class-conditioned configurations and resolves a budget
to weak steps the same way, so its counts are the DiT's own."""
from chipbench import spec

_dit = spec.load_module(spec.HERE / "configs" / "dit_work.py")

step_modes = _dit.step_modes
request_flops = _dit.request_flops
request_attention = _dit.request_attention
request_block_flops = _dit.request_block_flops
