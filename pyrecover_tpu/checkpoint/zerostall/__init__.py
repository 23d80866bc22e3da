"""Zero-stall checkpoint engine: async snapshot pipeline + content-
addressed incremental chunk store + in-RAM emergency tier.

The third checkpoint engine (``--checkpoint-engine zerostall``). Layout
under the experiment directory::

    <exp_dir>/ckpt_<step>[_final].zs.json    one manifest per checkpoint
    <exp_dir>/chunks/<dd>/<digest>           content-addressed chunks

``snapshot.py`` owns the save pipeline (donated-buffer-safe device→host
snapshot overlapped with training, bounded in-flight queue with a loud
``ckpt_backpressure`` event), ``chunkstore.py`` the incremental store +
refcounted GC, ``emergency.py`` the in-RAM restore tier. See the README
"Zero-stall checkpointing" section for the failure matrix.
"""

from pathlib import Path

from pyrecover_tpu.checkpoint.engine import (
    PARAMS_PREFIX,
    HandleEngine,
    nest_params,
)
from pyrecover_tpu.checkpoint.vanilla import _dtype_from_str
from pyrecover_tpu.checkpoint.zerostall import chunkstore, emergency
from pyrecover_tpu.checkpoint.zerostall.chunkstore import (
    collect_garbage,
    read_manifest,
    referenced_digests,
)
from pyrecover_tpu.checkpoint.zerostall.snapshot import (
    ZerostallSaveHandle,
    load_ckpt_zerostall,
    precheck_ckpt_zerostall,
    save_ckpt_zerostall,
)



class ZerostallEngine(HandleEngine):
    """This package's functions behind the engines' interface. Its own
    depth-1 queue back-pressures too; the join at the top of ``save``
    keeps the handles' shadow accounting in order."""

    name = "zerostall"
    _save = staticmethod(save_ckpt_zerostall)
    _precheck = staticmethod(precheck_ckpt_zerostall)

    def load(self, path, target_state, *, prechecked=False):
        # chunk reads re-verify their content digests; leaves assemble
        # host-side and device_put onto the TARGET shardings (elastic
        # execution identical to vanilla)
        return load_ckpt_zerostall(path, target_state)

    def read_params(self, path):
        store = chunkstore.ChunkStore(Path(path).parent)
        return nest_params(
            (entry["path"], chunkstore.assemble_leaf(
                store, entry, _dtype_from_str(entry["dtype"])))
            for entry in read_manifest(path)["leaves"]
            if entry["path"].startswith(PARAMS_PREFIX)
        )

    def ram_tier(self, exp_dir):
        return emergency.RamTier(exp_dir)


__all__ = [
    "ZerostallEngine",
    "chunkstore",
    "emergency",
    "save_ckpt_zerostall",
    "load_ckpt_zerostall",
    "precheck_ckpt_zerostall",
    "ZerostallSaveHandle",
    "collect_garbage",
    "referenced_digests",
    "read_manifest",
]
