import pathlib
import sys

# the program under test, as run.py finds it
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2] / "src"))
