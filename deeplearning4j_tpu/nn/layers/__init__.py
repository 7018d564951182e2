"""Layer configs/implementations.

Importing this package registers every built-in layer type with the config
serde registry (the analogue of the reference's Jackson subtype list), so
JSON round-trips work regardless of which layer module the user touched
first.
"""

from . import attention  # noqa: F401
from . import base  # noqa: F401
from . import convolution  # noqa: F401
from . import core  # noqa: F401
from . import decoder  # noqa: F401
from . import normalization  # noqa: F401
from . import pooling  # noqa: F401
from . import pretrain  # noqa: F401
from . import recurrent  # noqa: F401
from . import training  # noqa: F401
from . import variational  # noqa: F401
