import os
import sys

# graphlint's tensor-parallel serving-step registry entry traces a
# program jitted over a 2-device mesh; the CLI requests the virtual
# CPU mesh (the same mechanism the tests' conftest and the MULTICHIP
# dry-runs use) BEFORE jax's backend initializes.  Deliberately HERE
# and not in the package __init__: importing the library must not
# mutate process-global topology for hosts that never trace the tp
# program (the tp spec builder raises a clear error if devices are
# short at trace time).
import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 8)

from .runner import main  # noqa: E402

sys.exit(main())
