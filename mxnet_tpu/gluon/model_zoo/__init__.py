"""Model zoo (reference: python/mxnet/gluon/model_zoo/__init__.py)."""
from . import vision
from .pattern_lm import PatternLM
from .model_store import get_model_file, purge
