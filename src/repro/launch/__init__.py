from .mesh import PEAKS, make_mesh, make_production_mesh, peaks
