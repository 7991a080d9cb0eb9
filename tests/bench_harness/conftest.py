import os
import sys

# The benchmark is the package ``bench`` at the root of the checkout.
sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..")))
