from spark_rapids_tpu.utils.arm import close_on_except, safe_close, with_resource  # noqa: F401
from spark_rapids_tpu.utils.tracing import TraceRange  # noqa: F401
