"""``python -m repro`` entry point (see repro.api.cli)."""

import sys

from repro.api.cli import main
from repro.compile_cache import enable_compile_cache

if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
