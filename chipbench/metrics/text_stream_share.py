"""The text stream's share of the DiT blocks' device time, in %: the own
device time of the ops under ``dit_block/context`` (every op of the
joint block's text stream: its adaLN, q/k/v with their QK-norm, output
projection and MLP; ``core/packing.py`` ``_packed_joint_block``) over
that of every op under ``dit_block``.

The trace reduction keeps only the harness's shared scopes in an op's
path (``enginetrace.SCOPES``), which ``context`` is not among. The joint
block puts each of its ops under ``adaln``, ``attn``, ``mlp`` or
``context``, so the ops whose path is ``dit_block`` alone are the text
stream's."""
from chipbench import enginetrace


def read(run):
    eng = enginetrace.engine_of(run, scoped=True)
    if eng is None:
        return None
    by = eng["device_s_by_scope"]
    block = sum(s for path, s in by.items()
                if path.split("/")[0] == enginetrace.BLOCK_SCOPE)
    text = by.get(enginetrace.BLOCK_SCOPE, 0.0)
    if block <= 0 or text <= 0:
        return None
    return 100.0 * text / block
