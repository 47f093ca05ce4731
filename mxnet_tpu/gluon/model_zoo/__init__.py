"""Model zoo (reference: ``python/mxnet/gluon/model_zoo/`` [unverified];
language models mirror the GluonNLP-era workloads in BASELINE.md)."""

from . import vision  # noqa: F401
from . import bert  # noqa: F401
from . import transformer  # noqa: F401
from . import keye  # noqa: F401
from . import granite_hybrid  # noqa: F401
from . import latent_lm  # noqa: F401
from . import joyai  # noqa: F401
from . import xing  # noqa: F401
from . import ouro  # noqa: F401
from . import zaya  # noqa: F401
from . import olmo_hybrid  # noqa: F401
from . import ssd  # noqa: F401
from . import faster_rcnn  # noqa: F401

__all__ = ["vision", "bert", "transformer", "keye", "granite_hybrid",
           "latent_lm", "joyai", "xing", "ouro", "zaya", "olmo_hybrid", "ssd",
           "faster_rcnn"]
