import sys

from ..utils.compile_cache import configure_compile_cache
from .cli import main

# the executable places the persistent compile cache (a worker with a real
# engine compiles the v2 programs; in-process callers of main() do not get
# one, like the rest of the test suite)
configure_compile_cache()
sys.exit(main())
