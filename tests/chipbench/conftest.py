import os
import sys

# the benchmark's own modules live at the root of the checkout
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
