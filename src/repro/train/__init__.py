from .optim import (OptConfig, OptState, adamw_update, decay_mask,  # noqa: F401
                    init_opt_state)
from .step import make_train_step  # noqa: F401
