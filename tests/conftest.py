import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# NOTE: no XLA_FLAGS here on purpose — smoke tests must see 1 device;
# multi-device tests spawn subprocesses that set their own flags.

