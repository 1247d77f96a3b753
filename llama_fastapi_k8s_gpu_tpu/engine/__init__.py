from .continuous import ContinuousEngine  # noqa: F401
from .engine import Engine  # noqa: F401
from .fake import FakeEngine  # noqa: F401
from .watchdog import Watchdog  # noqa: F401
