"""The dense family: decoder blocks of grouped-query attention and a
SwiGLU MLP.  Its weights and their layout in the program are
``weights.py``'s, its plain reference ``reference.py``'s."""
# the keys of the configuration file that set the program's sizes: each
# agrees with the program's registered configuration unless it is listed
# as reduced
SIZE_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
             "d_ff", "vocab", "rope_theta", "norm_eps", "dtype")

from reference import control_gaps, served_gaps, train  # noqa: F401
from weights import dense_shapes as shapes  # noqa: F401
from weights import dense_weights as weights  # noqa: F401
from weights import from_program, to_program  # noqa: F401
