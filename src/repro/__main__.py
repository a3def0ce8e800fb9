"""Entry point: ``python -m repro <check|inspect|verify|gc|restore>``."""
import sys

from repro.cli import main
from repro.launch.compile_cache import use_compile_cache

if __name__ == "__main__":
    use_compile_cache()
    sys.exit(main())
