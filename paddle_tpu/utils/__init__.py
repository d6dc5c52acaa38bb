"""Aux subsystems (SURVEY.md §2.11): debugging, failure detection,
determinism, memory introspection, self-test, model stats."""

from . import debugger
from . import image_util
from . import plot
from . import show_pb
from . import timeline
from . import nan_check
from . import determinism
from . import memory
from . import install_check
from . import log
from . import model_stat
