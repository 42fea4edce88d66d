"""The kinds a run of the layer pattern is made of: name -> the record
the kind's own module ends in (``common.LayerKind``).  A new kind is its
module and one line here."""

from __future__ import annotations

from typing import Any, Dict, Tuple

from ray_tpu.models.common import DENSE, NONE
from ray_tpu.models.diff_attention import DIFF
from ray_tpu.models.gdn import GDN
from ray_tpu.models.kda import KDA
from ray_tpu.models.mamba import GMU, MAMBA
from ray_tpu.models.mamba2 import MAMBA2
from ray_tpu.models.mha import MHA
from ray_tpu.models.mla import MLA
from ray_tpu.models.moe import MOE

ATTENTION = {kind.name: kind for kind in (MHA, MLA, GDN, MAMBA, GMU, DIFF,
                                           MAMBA2, KDA)}
FFN = {kind.name: kind for kind in (DENSE, MOE, NONE)}


def run_options(attention: str) -> Tuple[str, Dict[str, Any]]:
    """A run's first word, ``"kind"`` or ``"kind:option,option"`` ->
    (kind, its options): ``window`` an int, ``writes`` / ``reads`` the
    slot's name.  Anything a kind does not take is refused."""
    kind, _, rest = str(attention).partition(":")
    if kind not in ATTENTION:
        raise ValueError(f"layer pattern kind {attention!r}: one of "
                         f"{tuple(ATTENTION)}")
    takes = ATTENTION[kind].options
    options: Dict[str, Any] = {}
    for word in filter(None, rest.split(",")):
        name, _, value = word.partition("=")
        if name not in takes or name in options or not value or (
                isinstance(takes[name], tuple) and value not in takes[name]):
            raise ValueError(f"a {kind!r} run does not take {word!r}")
        options[name] = int(value) if takes[name] is int else value
    if "reads" in options and "writes" in options:
        raise ValueError(f"{attention!r} reads the slot it writes")
    return kind, {**options, **ATTENTION[kind].implied}
