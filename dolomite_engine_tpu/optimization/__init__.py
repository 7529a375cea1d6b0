from .optimizer import build_optimizer_from_args, get_mup_label_tree, get_optimizer
from .scheduler import get_scheduler, get_scheduler_factor
