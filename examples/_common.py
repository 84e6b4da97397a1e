"""Shared example bootstrap: repo-root import path + compile cache."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deeplearning4j_tpu.runtime.compilecache import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()
