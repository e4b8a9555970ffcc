import sys

from repro.experiments.cli import main
from repro.utils.compile_cache import enable_compile_cache

enable_compile_cache()
sys.exit(main())
