"""Shared CLI bootstrap: puts the repo on sys.path, handles the --cpu
flag (JAX's CPU backend instead of the chip) and places the
persistent compile cache."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from gelly_streaming_tpu.core.platform import enable_compile_cache, use_cpu  # noqa: E402

if "--cpu" in sys.argv:
    sys.argv.remove("--cpu")
    use_cpu()
enable_compile_cache()
