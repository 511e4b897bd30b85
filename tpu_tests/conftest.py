"""On-chip suite plumbing: repo root on sys.path + the persistent
compilation cache every entry point shares, so kernel-suite compiles
are reused by the other commands of the same tool call. The platform
check is in test_on_chip.py — nothing here initializes a backend."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pbs_tpu.utils.compile_cache import setup_compilation_cache  # noqa: E402

setup_compilation_cache()
