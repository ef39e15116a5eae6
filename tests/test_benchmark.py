from benchmark.tests.test_benchmark import *  # noqa: F401,F403
from benchmark.tests.test_program_span import *  # noqa: F401,F403
from benchmark.tests.test_scope_time import *  # noqa: F401,F403
from benchmark.tests.test_jamba_costs import *  # noqa: F401,F403
