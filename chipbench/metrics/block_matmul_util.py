"""The DiT blocks' matmul utilisation, in %: the needed dense block
work of the window's request-steps (:mod:`chipbench.blockwork`) over
the device time of every op the trace places under the ``dit_block``
scope (``core/packing.py`` ``_packed_block``), less the
``flash_attention`` kernel's, times the device's bf16 peak. An op's
scope is its HLO ``op_name`` metadata; for a fusion, that of the
instruction XLA gave the fusion."""
from chipbench import enginetrace


def read(run):
    eng = enginetrace.engine_of(run, scoped=True)
    flops = run["work"].get("block_flops")
    if eng is None or run["peak"] is None or not flops \
            or eng["block_s"] <= 0:
        return None
    return 100.0 * flops / (eng["block_s"] * run["peak"]["bf16_flops"])
