import os
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path[:0] = [os.path.abspath(ROOT), os.path.abspath(os.path.join(ROOT, "src"))]
