"""bigdl_tpu.nn — the layer & criterion zoo.

TPU-native re-design of the reference's nn/ package (234 Torch-style
layers, spark/dl/.../nn/).  Every public class mirrors a reference layer
by name and semantics; docstrings cite the Scala file they correspond to.
"""

from bigdl_tpu.nn.activation import *      # noqa: F401,F403
from bigdl_tpu.nn.linear import *          # noqa: F401,F403
from bigdl_tpu.nn.containers import *      # noqa: F401,F403
from bigdl_tpu.nn.shape_ops import *       # noqa: F401,F403
from bigdl_tpu.nn.table_ops import *       # noqa: F401,F403
from bigdl_tpu.nn.conv import *            # noqa: F401,F403
from bigdl_tpu.nn.pooling import *         # noqa: F401,F403
from bigdl_tpu.nn.normalization import *   # noqa: F401,F403
from bigdl_tpu.nn.regularization import *  # noqa: F401,F403
from bigdl_tpu.nn.criterion import *       # noqa: F401,F403
from bigdl_tpu.nn.rnn import *             # noqa: F401,F403
from bigdl_tpu.nn.attention import *       # noqa: F401,F403
from bigdl_tpu.nn.latent_attention import *  # noqa: F401,F403
from bigdl_tpu.nn.differential_attention import *  # noqa: F401,F403
from bigdl_tpu.nn.moe import *             # noqa: F401,F403
from bigdl_tpu.nn.ssm import *             # noqa: F401,F403
from bigdl_tpu.nn.short_conv import *      # noqa: F401,F403
from bigdl_tpu.nn.quantized import *       # noqa: F401,F403
from bigdl_tpu.nn.detection import *       # noqa: F401,F403
from bigdl_tpu.nn.sparse import *          # noqa: F401,F403
from bigdl_tpu.nn.tree import *            # noqa: F401,F403
