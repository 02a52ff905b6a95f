"""A block family that the harness's tests add by this file alone: the
dense block under another name."""
from blocks.dense import *  # noqa: F401,F403
