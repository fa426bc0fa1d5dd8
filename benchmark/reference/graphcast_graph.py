"""The GraphCast graphs, built by the benchmark for its plain reference.

A copy of the generator in ``dgraph_tpu/models/graphcast/mesh.py`` and of
the static features in ``.../graph.py`` (icosahedron in the paper's
orientation, 4-to-1 subdivision with parents kept as a prefix, the multimesh
as the union of every level's bidirectional edges, grid-to-mesh edges to the
<= 4 nearest mesh vertices within 0.6 of the finest edge length, mesh-to-grid
edges from the 3 vertices of the nearest face). Everything is in the caller's
numbering: no partition, no renumbering, no padding. Imports nothing of the
program.
"""

from __future__ import annotations

import numpy as np
from scipy.spatial import ConvexHull, cKDTree


def icosahedron():
    phi = (1.0 + np.sqrt(5.0)) / 2.0
    verts = []
    for c1 in (1.0, -1.0):
        for c2 in (phi, -phi):
            verts.extend([(c1, c2, 0.0), (0.0, c1, c2), (c2, 0.0, c1)])
    verts = np.asarray(verts, dtype=np.float64)
    verts /= np.linalg.norm([1.0, phi])
    angle = (np.pi - 2.0 * np.arcsin(phi / np.sqrt(3.0))) / 2.0
    c, s = np.cos(angle), np.sin(angle)
    verts = verts @ np.array([[c, 0.0, s], [0.0, 1.0, 0.0], [-s, 0.0, c]])
    faces = ConvexHull(verts).simplices.astype(np.int64)
    n = np.cross(verts[faces[:, 1]] - verts[faces[:, 0]],
                 verts[faces[:, 2]] - verts[faces[:, 0]])
    flip = (n * verts[faces].mean(axis=1)).sum(axis=1) < 0
    faces[flip] = faces[flip][:, ::-1]
    return verts, faces


def subdivide(verts, faces):
    edge_mid, appended, next_id = {}, [], len(verts)

    def midpoint(a, b):
        nonlocal next_id
        key = (a, b) if a < b else (b, a)
        if key not in edge_mid:
            m = verts[a] + verts[b]
            appended.append(m / np.linalg.norm(m))
            edge_mid[key] = next_id
            next_id += 1
        return edge_mid[key]

    new_faces = np.empty((len(faces) * 4, 3), dtype=np.int64)
    for i, (a, b, c) in enumerate(faces):
        ab, bc, ca = midpoint(a, b), midpoint(b, c), midpoint(c, a)
        new_faces[4 * i:4 * i + 4] = (
            (a, ab, ca), (b, bc, ab), (c, ca, bc), (ab, bc, ca))
    return np.concatenate([verts, np.asarray(appended)], axis=0), new_faces


def faces_to_edges(faces):
    e = np.concatenate([faces[:, [0, 1]], faces[:, [1, 2]], faces[:, [2, 0]]])
    e = np.unique(np.concatenate([e, e[:, ::-1]]), axis=0)
    return e.T.copy()


def latlon_to_xyz(latlon):
    lat, lon = np.deg2rad(latlon[:, 0]), np.deg2rad(latlon[:, 1])
    return np.stack([np.cos(lat) * np.cos(lon), np.cos(lat) * np.sin(lon),
                     np.sin(lat)], axis=1)


def xyz_to_latlon(xyz):
    lat = np.rad2deg(np.arcsin(np.clip(xyz[:, 2], -1, 1)))
    lon = np.rad2deg(np.arctan2(xyz[:, 1], xyz[:, 0])) % 360.0
    return np.stack([lat, lon], axis=1)


def node_static(latlon):
    lat, lon = np.deg2rad(latlon[:, 0]), np.deg2rad(latlon[:, 1])
    return np.stack([np.cos(lat), np.sin(lon) * np.cos(lat),
                     np.cos(lon) * np.cos(lat), np.sin(lat)],
                    axis=1).astype(np.float32)


def edge_static(src_xyz, dst_xyz, edges):
    d = src_xyz[edges[0]] - dst_xyz[edges[1]]
    length = np.linalg.norm(d, axis=1, keepdims=True)
    scale = max(length.max(), 1e-12)
    return np.concatenate([length / scale, d / scale], axis=1).astype(np.float32)


def build(mesh_level: int, num_lat: int, num_lon: int) -> dict:
    """Edge lists ([2, E], src row then dst row) and static features."""
    verts, faces = icosahedron()
    edge_sets = [faces_to_edges(faces)]
    for _ in range(mesh_level):
        verts, faces = subdivide(verts, faces)
        edge_sets.append(faces_to_edges(faces))
    mesh_edges = np.unique(np.concatenate(edge_sets, axis=1).T, axis=0).T.copy()

    lats = np.linspace(90.0, -90.0, num_lat)
    lons = np.linspace(0.0, 360.0, num_lon, endpoint=False)
    lat_g, lon_g = np.meshgrid(lats, lons, indexing="ij")
    grid_latlon = np.stack([lat_g.ravel(), lon_g.ravel()], axis=1)
    grid_xyz = latlon_to_xyz(grid_latlon)

    finest = faces_to_edges(faces)
    radius = 0.6 * np.linalg.norm(
        verts[finest[0]] - verts[finest[1]], axis=1).max()
    dist, idx = cKDTree(verts).query(grid_xyz, k=4, workers=-1)
    near = dist < radius
    g2m = np.stack([
        np.broadcast_to(np.arange(len(grid_xyz))[:, None], idx.shape)[near],
        idx[near]]).astype(np.int64)

    centroids = verts[faces].mean(axis=1)
    centroids /= np.linalg.norm(centroids, axis=1, keepdims=True)
    _, fidx = cKDTree(centroids).query(grid_xyz, k=1)
    m2g = np.stack([faces[fidx].ravel(),
                    np.repeat(np.arange(len(grid_xyz)), 3)]).astype(np.int64)
    return {
        "num_grid": len(grid_xyz), "num_mesh": len(verts),
        "mesh_edges": mesh_edges, "g2m_edges": g2m, "m2g_edges": m2g,
        "grid_node_static": node_static(grid_latlon),
        "mesh_node_static": node_static(xyz_to_latlon(verts)),
        "mesh_edge_static": edge_static(verts, verts, mesh_edges),
        "g2m_edge_static": edge_static(grid_xyz, verts, g2m),
        "m2g_edge_static": edge_static(verts, grid_xyz, m2g),
    }
