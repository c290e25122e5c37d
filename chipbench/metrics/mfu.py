"""Model FLOP/s utilisation of the window, in %: the needed model work of
the request-steps the window completed (useful tokens, CFG counted,
dense per-segment attention, no padding) over window seconds times chips
times the device's bf16 peak (packed step: ``pipeline/packed.py``,
``models/dit.py``)."""


def read(run):
    if run["peak"] is None or not run["work"]["model_flops"]:
        return None
    return 100.0 * run["work"]["model_flops"] / (
        run["window_s"] * run["chips"] * run["peak"]["bf16_flops"])
