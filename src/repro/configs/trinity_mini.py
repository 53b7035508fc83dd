"""Config module for --arch trinity-mini (served through the paged engine)."""
from repro.configs.archs import TRINITY_MINI as CONFIG

CONFIG = CONFIG
