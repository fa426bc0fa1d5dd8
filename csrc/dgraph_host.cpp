// Native host-side graph toolkit for dgraph_tpu.
//
// Role: the TPU-native counterpart of the reference's native layer. The
// reference's C++/CUDA lives in the device path
// (DGraph/distributed/csrc/*: gather/scatter kernels, NVSHMEM runtime); on
// TPU the device path is XLA/Pallas, so native code belongs where Python is
// actually the bottleneck: HOST-side plan building and partitioning of
// billion-edge graphs (SURVEY.md §7 "papers100M plan build memory/time").
//
// Exposed via a plain C ABI and loaded with ctypes (no pybind11 in this
// environment). Every entry point has a numpy fallback in
// dgraph_tpu/partition.py / plan.py — the reference's dual
// native/fallback pattern (RankLocalOps.py:21-31).

#include <algorithm>
#include <atomic>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <queue>
#include <random>
#include <thread>
#include <vector>

extern "C" {

// Build an undirected CSR adjacency from a directed edge list.
// indptr must hold V+1 entries; if indices == nullptr, only fills indptr
// (call once to size, once to fill).
void build_sym_csr(const int64_t* src, const int64_t* dst, int64_t num_edges,
                   int64_t num_vertices, int64_t* indptr, int64_t* indices) {
  std::vector<int64_t> deg(num_vertices, 0);
  for (int64_t e = 0; e < num_edges; ++e) {
    ++deg[src[e]];
    ++deg[dst[e]];
  }
  indptr[0] = 0;
  for (int64_t v = 0; v < num_vertices; ++v) indptr[v + 1] = indptr[v] + deg[v];
  if (!indices) return;
  std::vector<int64_t> cur(indptr, indptr + num_vertices);
  for (int64_t e = 0; e < num_edges; ++e) {
    indices[cur[src[e]]++] = dst[e];
    indices[cur[dst[e]]++] = src[e];
  }
}

// Greedy BFS region-growing partition with hard balance cap — the METIS
// substitute for very large graphs. Deterministic for a fixed seed.
void greedy_bfs_partition(const int64_t* src, const int64_t* dst,
                          int64_t num_edges, int64_t num_vertices,
                          int32_t world_size, uint64_t seed, int32_t* out_part) {
  std::vector<int64_t> indptr(num_vertices + 1);
  std::vector<int64_t> indices;
  build_sym_csr(src, dst, num_edges, num_vertices, indptr.data(), nullptr);
  indices.resize(indptr[num_vertices]);
  build_sym_csr(src, dst, num_edges, num_vertices, indptr.data(), indices.data());

  std::fill(out_part, out_part + num_vertices, -1);
  std::vector<int64_t> order(num_vertices);
  for (int64_t i = 0; i < num_vertices; ++i) order[i] = i;
  std::mt19937_64 rng(seed);
  std::shuffle(order.begin(), order.end(), rng);

  const int64_t cap = (num_vertices + world_size - 1) / world_size;
  int64_t seed_ptr = 0;
  std::vector<int64_t> stack;
  stack.reserve(1024);
  for (int32_t r = 0; r < world_size; ++r) {
    int64_t count = 0;
    stack.clear();
    while (count < cap) {
      if (stack.empty()) {
        while (seed_ptr < num_vertices && out_part[order[seed_ptr]] >= 0) ++seed_ptr;
        if (seed_ptr >= num_vertices) break;
        stack.push_back(order[seed_ptr]);
      }
      int64_t v = stack.back();
      stack.pop_back();
      if (out_part[v] >= 0) continue;
      out_part[v] = r;
      ++count;
      for (int64_t k = indptr[v]; k < indptr[v + 1]; ++k) {
        int64_t n = indices[k];
        if (out_part[n] < 0) stack.push_back(n);
      }
    }
  }
  for (int64_t v = 0; v < num_vertices; ++v)
    if (out_part[v] < 0) out_part[v] = world_size - 1;
}

namespace {

// Weighted undirected graph in CSR form for the multilevel partitioner.
struct WGraph {
  int64_t nv = 0;
  std::vector<int64_t> indptr;
  std::vector<int64_t> adj;   // neighbor ids (deduped, no self loops)
  std::vector<int64_t> ew;    // edge weights (parallel-edge multiplicity)
  std::vector<int64_t> vw;    // vertex weights (coarse vertices aggregate)
};

// Build a WGraph from UNIQUE UNDIRECTED weighted pairs (u < v, no self
// loops, no duplicates — the contract the chunked numpy contraction in
// partition.multilevel_big_partition delivers) plus per-vertex weights.
// Both directions are inserted directly; no dedup pass needed.
WGraph build_wgraph_weighted(const int64_t* usrc, const int64_t* udst,
                             const int64_t* uw, int64_t num_pairs,
                             const int64_t* vw, int64_t num_vertices) {
  WGraph g;
  g.nv = num_vertices;
  g.vw.assign(vw, vw + num_vertices);
  std::vector<int64_t> deg(num_vertices, 0);
  for (int64_t e = 0; e < num_pairs; ++e) {
    ++deg[usrc[e]];
    ++deg[udst[e]];
  }
  g.indptr.assign(num_vertices + 1, 0);
  for (int64_t v = 0; v < num_vertices; ++v)
    g.indptr[v + 1] = g.indptr[v] + deg[v];
  g.adj.assign(g.indptr[num_vertices], 0);
  g.ew.assign(g.indptr[num_vertices], 0);
  std::vector<int64_t> cur(g.indptr.begin(), g.indptr.end() - 1);
  for (int64_t e = 0; e < num_pairs; ++e) {
    const int64_t a = usrc[e], b = udst[e], w = uw[e];
    g.adj[cur[a]] = b; g.ew[cur[a]++] = w;
    g.adj[cur[b]] = a; g.ew[cur[b]++] = w;
  }
  return g;
}

// Build the level-0 weighted graph from a directed edge list: symmetrize,
// drop self loops, merge parallel edges into weights.
WGraph build_wgraph(const int64_t* src, const int64_t* dst, int64_t num_edges,
                    int64_t num_vertices) {
  WGraph g;
  g.nv = num_vertices;
  g.vw.assign(num_vertices, 1);
  std::vector<int64_t> deg(num_vertices, 0);
  for (int64_t e = 0; e < num_edges; ++e) {
    if (src[e] == dst[e]) continue;
    ++deg[src[e]];
    ++deg[dst[e]];
  }
  g.indptr.assign(num_vertices + 1, 0);
  for (int64_t v = 0; v < num_vertices; ++v) g.indptr[v + 1] = g.indptr[v] + deg[v];
  std::vector<int64_t> raw(g.indptr[num_vertices]);
  std::vector<int64_t> cur(g.indptr.begin(), g.indptr.end() - 1);
  for (int64_t e = 0; e < num_edges; ++e) {
    if (src[e] == dst[e]) continue;
    raw[cur[src[e]]++] = dst[e];
    raw[cur[dst[e]]++] = src[e];
  }
  // dedup neighbors per vertex, accumulating multiplicity as weight
  g.adj.reserve(raw.size());
  g.ew.reserve(raw.size());
  std::vector<int64_t> new_indptr(num_vertices + 1, 0);
  for (int64_t v = 0; v < num_vertices; ++v) {
    int64_t lo = g.indptr[v], hi = g.indptr[v + 1];
    std::sort(raw.begin() + lo, raw.begin() + hi);
    for (int64_t k = lo; k < hi;) {
      int64_t n = raw[k], w = 0;
      while (k < hi && raw[k] == n) { ++w; ++k; }
      g.adj.push_back(n);
      g.ew.push_back(w);
    }
    new_indptr[v + 1] = static_cast<int64_t>(g.adj.size());
  }
  g.indptr = std::move(new_indptr);
  return g;
}

// Heavy-edge matching: returns match[v] (== v for unmatched/self-matched)
// and the number of coarse vertices; cmap[v] = coarse id. max_vw > 0
// hard-bounds the merged vertex weight — without it a giant supernode can
// exceed the initial partition's per-rank cap, and region growth then
// overshoots by that whole supernode (observed 1.27x imbalance on a
// half-sampled 120k power-law; METIS bounds supernode weight the same way).
int64_t heavy_edge_matching(const WGraph& g, std::mt19937_64& rng,
                            std::vector<int64_t>& cmap,
                            int64_t max_vw = 0) {
  // Visit low-degree vertices first (random within a degree class) and
  // score candidates by edge weight normalized by the partner's vertex
  // weight. Plain max-weight matching merges across weak bridges when all
  // weights tie (level 0) — bridge endpoints tend to have higher degree,
  // so degree-ordered visiting lets cluster-internal vertices pair up
  // before a bridge endpoint can grab them, and the normalization keeps
  // supernodes from snowballing.
  std::vector<int64_t> order(g.nv);
  for (int64_t i = 0; i < g.nv; ++i) order[i] = i;
  std::shuffle(order.begin(), order.end(), rng);
  std::stable_sort(order.begin(), order.end(), [&](int64_t a, int64_t b) {
    return (g.indptr[a + 1] - g.indptr[a]) < (g.indptr[b + 1] - g.indptr[b]);
  });
  std::vector<int64_t> match(g.nv, -1);
  for (int64_t idx = 0; idx < g.nv; ++idx) {
    int64_t v = order[idx];
    if (match[v] >= 0) continue;
    int64_t best = -1;
    double best_score = 0.0;
    for (int64_t k = g.indptr[v]; k < g.indptr[v + 1]; ++k) {
      int64_t n = g.adj[k];
      if (match[n] >= 0) continue;
      if (max_vw > 0 && g.vw[v] + g.vw[n] > max_vw) continue;
      double score = double(g.ew[k]) / double(g.vw[n]);
      if (score > best_score) { best = n; best_score = score; }
    }
    if (best >= 0) { match[v] = best; match[best] = v; }
    else match[v] = v;
  }
  cmap.assign(g.nv, -1);
  int64_t nc = 0;
  for (int64_t v = 0; v < g.nv; ++v) {
    if (cmap[v] >= 0) continue;
    cmap[v] = nc;
    if (match[v] != v) cmap[match[v]] = nc;
    ++nc;
  }
  return nc;
}

// Contract g by cmap into a coarse weighted graph.
WGraph contract(const WGraph& g, const std::vector<int64_t>& cmap, int64_t nc) {
  WGraph c;
  c.nv = nc;
  c.vw.assign(nc, 0);
  for (int64_t v = 0; v < g.nv; ++v) c.vw[cmap[v]] += g.vw[v];
  // gather coarse edges per coarse vertex, then dedup-accumulate
  std::vector<std::pair<int64_t, int64_t>> edges;  // (enc(cu,cv), w) cu<cv
  edges.reserve(g.adj.size() / 2);
  for (int64_t v = 0; v < g.nv; ++v) {
    int64_t cu = cmap[v];
    for (int64_t k = g.indptr[v]; k < g.indptr[v + 1]; ++k) {
      int64_t cv = cmap[g.adj[k]];
      if (cu < cv) edges.emplace_back(cu * nc + cv, g.ew[k]);
    }
  }
  std::sort(edges.begin(), edges.end());
  std::vector<int64_t> deg(nc, 0);
  std::vector<std::pair<int64_t, int64_t>> merged;  // (enc, w)
  merged.reserve(edges.size());
  for (size_t i = 0; i < edges.size();) {
    int64_t enc = edges[i].first, w = 0;
    while (i < edges.size() && edges[i].first == enc) { w += edges[i].second; ++i; }
    merged.emplace_back(enc, w);
    ++deg[enc / nc];
    ++deg[enc % nc];
  }
  c.indptr.assign(nc + 1, 0);
  for (int64_t v = 0; v < nc; ++v) c.indptr[v + 1] = c.indptr[v] + deg[v];
  c.adj.assign(c.indptr[nc], 0);
  c.ew.assign(c.indptr[nc], 0);
  std::vector<int64_t> cur(c.indptr.begin(), c.indptr.end() - 1);
  for (auto& [enc, w] : merged) {
    int64_t a = enc / nc, b = enc % nc;
    c.adj[cur[a]] = b; c.ew[cur[a]++] = w;
    c.adj[cur[b]] = a; c.ew[cur[b]++] = w;
  }
  return c;
}

// Weighted greedy region growing on the (coarsest) graph — METIS-style
// GGGP: always absorb the frontier vertex with the STRONGEST connection to
// the growing region. A DFS stack here is catastrophically order-sensitive
// (it dives along weak chain edges, stranding heavy partners on the stack);
// the max-connection heap follows the weight structure instead.
void initial_partition(const WGraph& g, int32_t world_size, std::mt19937_64& rng,
                       std::vector<int32_t>& part) {
  part.assign(g.nv, -1);
  int64_t total_vw = 0;
  for (auto w : g.vw) total_vw += w;
  const int64_t cap = (total_vw + world_size - 1) / world_size;
  std::vector<int64_t> order(g.nv);
  for (int64_t i = 0; i < g.nv; ++i) order[i] = i;
  std::shuffle(order.begin(), order.end(), rng);
  int64_t seed_ptr = 0;
  std::vector<int64_t> conn(g.nv, 0);
  // lazy max-heap of (connection-to-region, vertex); stale entries skipped
  std::priority_queue<std::pair<int64_t, int64_t>> heap;
  for (int32_t r = 0; r < world_size; ++r) {
    int64_t weight = 0;
    while (!heap.empty()) heap.pop();
    std::fill(conn.begin(), conn.end(), 0);
    while (weight < cap) {
      int64_t v = -1;
      while (!heap.empty()) {
        auto [w, u] = heap.top();
        heap.pop();
        if (part[u] < 0 && w == conn[u]) { v = u; break; }
      }
      if (v < 0) {
        while (seed_ptr < g.nv && part[order[seed_ptr]] >= 0) ++seed_ptr;
        if (seed_ptr >= g.nv) break;
        v = order[seed_ptr];
      }
      part[v] = r;
      weight += g.vw[v];
      for (int64_t k = g.indptr[v]; k < g.indptr[v + 1]; ++k) {
        int64_t n = g.adj[k];
        if (part[n] < 0) {
          conn[n] += g.ew[k];
          heap.emplace(conn[n], n);
        }
      }
    }
  }
  for (int64_t v = 0; v < g.nv; ++v)
    if (part[v] < 0) part[v] = world_size - 1;
}

// Force every rank under the balance cap: over-cap ranks shed vertices to
// the best under-cap neighbor rank (by connection, falling back to the
// most underfull rank). Gain-driven refinement can never FIX a violation
// — its feasibility check only refuses to create new ones — so this runs
// wherever an unbalanced partition can enter (initial growth overshoot,
// a projected partition from differently-weighted levels).
void rebalance_to_cap(const WGraph& g, int32_t world_size,
                      std::vector<int32_t>& part, double imbalance) {
  int64_t total_vw = 0;
  for (auto w : g.vw) total_vw += w;
  const int64_t cap =
      static_cast<int64_t>((double(total_vw) / world_size) * imbalance) + 1;
  std::vector<int64_t> pw(world_size, 0);
  for (int64_t v = 0; v < g.nv; ++v) pw[part[v]] += g.vw[v];
  std::vector<int64_t> conn(world_size, 0);
  for (int sweep = 0; sweep < 8; ++sweep) {
    bool over = false;
    for (int32_t r = 0; r < world_size; ++r) over |= pw[r] > cap;
    if (!over) return;
    bool moved = false;
    for (int64_t v = 0; v < g.nv; ++v) {
      const int32_t pv = part[v];
      if (pw[pv] <= cap) continue;
      std::fill(conn.begin(), conn.end(), 0);
      for (int64_t k = g.indptr[v]; k < g.indptr[v + 1]; ++k)
        conn[part[g.adj[k]]] += g.ew[k];
      int32_t best = -1;
      int64_t best_conn = -1, best_pw = INT64_MAX;
      for (int32_t r = 0; r < world_size; ++r) {
        if (r == pv || pw[r] + g.vw[v] > cap) continue;
        // prefer connection, tie-break toward the most underfull rank
        if (conn[r] > best_conn ||
            (conn[r] == best_conn && pw[r] < best_pw)) {
          best = r;
          best_conn = conn[r];
          best_pw = pw[r];
        }
      }
      if (best >= 0) {
        pw[pv] -= g.vw[v];
        pw[best] += g.vw[v];
        part[v] = best;
        moved = true;
      }
    }
    if (!moved) return;  // nothing placeable (oversized vertices)
  }
}

// Greedy boundary refinement (FM-lite): move boundary vertices to the
// neighbor partition with the largest positive cut gain, under a balance
// cap. A few passes per level.
void refine(const WGraph& g, int32_t world_size, std::vector<int32_t>& part,
            int passes, double imbalance) {
  int64_t total_vw = 0;
  for (auto w : g.vw) total_vw += w;
  const int64_t cap =
      static_cast<int64_t>((double(total_vw) / world_size) * imbalance) + 1;
  std::vector<int64_t> pw(world_size, 0);
  for (int64_t v = 0; v < g.nv; ++v) pw[part[v]] += g.vw[v];
  std::vector<int64_t> conn(world_size, 0);
  for (int p = 0; p < passes; ++p) {
    int64_t moves = 0;
    for (int64_t v = 0; v < g.nv; ++v) {
      int32_t pv = part[v];
      bool boundary = false;
      for (int64_t k = g.indptr[v]; k < g.indptr[v + 1]; ++k)
        if (part[g.adj[k]] != pv) { boundary = true; break; }
      if (!boundary) continue;
      std::fill(conn.begin(), conn.end(), 0);
      for (int64_t k = g.indptr[v]; k < g.indptr[v + 1]; ++k)
        conn[part[g.adj[k]]] += g.ew[k];
      int32_t best = pv;
      int64_t best_gain = 0;
      for (int32_t r = 0; r < world_size; ++r) {
        if (r == pv || pw[r] + g.vw[v] > cap) continue;
        int64_t gain = conn[r] - conn[pv];
        if (gain > best_gain) { best = r; best_gain = gain; }
      }
      if (best != pv) {
        pw[pv] -= g.vw[v];
        pw[best] += g.vw[v];
        part[v] = best;
        ++moves;
      }
    }
    if (!moves) break;
  }
}

// Shared setup for the table-based refiners: env-tunable memory gate for
// the [nv, W] connection table, balance cap, per-rank weights, and the
// table itself (conn[v*W + r] = edge weight from v into rank r).
// Returns false when the table would exceed the gate (strtoll saturates
// on out-of-range input — atoll is UB there; the clamp keeps <<30 from
// overflowing into a negative gate that would silently disable the
// refiner everywhere).
bool build_conn_table(const WGraph& g, int32_t W,
                      const std::vector<int32_t>& part, double imbalance,
                      int64_t* cap_out, std::vector<int64_t>& pw,
                      std::vector<int64_t>& conn) {
  int64_t gate_gb = 6;
  if (const char* ge = std::getenv("DGRAPH_HOST_FM_TABLE_GB")) {
    const int64_t v = std::strtoll(ge, nullptr, 10);
    if (v > 0) gate_gb = std::min<int64_t>(v, int64_t(1) << 20);
  }
  if (g.nv * int64_t(W) * 8 > (gate_gb << 30)) return false;
  int64_t total_vw = 0;
  for (auto w : g.vw) total_vw += w;
  *cap_out = static_cast<int64_t>((double(total_vw) / W) * imbalance) + 1;
  pw.assign(W, 0);
  for (int64_t v = 0; v < g.nv; ++v) pw[part[v]] += g.vw[v];
  conn.assign(size_t(g.nv) * W, 0);
  for (int64_t v = 0; v < g.nv; ++v)
    for (int64_t k = g.indptr[v]; k < g.indptr[v + 1]; ++k)
      conn[size_t(v) * W + part[g.adj[k]]] += g.ew[k];
  return true;
}

// Proper FM (KL/FM-class) k-way refinement with hill climbing: moves are
// taken in gain order from a lazy max-heap, each vertex moves at most once
// per pass, NEGATIVE-gain moves are allowed, and the pass rolls back to
// the best cumulative-cut prefix. This escapes the local minima the
// positive-gain-only refine() above gets stuck in — the difference between
// "26% better than random" and METIS-class cuts (VERDICT r3 #6).
//
// Cost model (the classic FM implementation): a [nv, W] connection table
// updated incrementally — O(deg) per applied move, O(W) per gain read —
// instead of recomputing neighbor gains from adjacency (O(deg^2) per move,
// which power-law hubs turn quadratic). Levels whose table would exceed
// the memory gate skip FM and keep the greedy refine result.
void fm_refine_impl(const WGraph& g, int32_t W, std::vector<int32_t>& part,
                    int passes, int64_t cap, std::vector<int64_t>& pw,
                    std::vector<int64_t>& conn) {
  std::vector<uint8_t> locked(g.nv, 0);
  std::vector<int64_t> cur_gain(g.nv, INT64_MIN);

  // best balance-feasible move for v from its conn row; INT64_MIN when
  // interior or nothing feasible
  auto best_from_row = [&](int64_t v, int32_t* out_r) -> int64_t {
    const int32_t pv = part[v];
    const int64_t* row = conn.data() + size_t(v) * W;
    int32_t best = pv;
    int64_t best_gain = INT64_MIN;
    for (int32_t r = 0; r < W; ++r) {
      if (r == pv || (row[r] == 0 && best_gain != INT64_MIN)) continue;
      if (pw[r] + g.vw[v] > cap) continue;
      const int64_t gain = row[r] - row[pv];
      if (gain > best_gain) { best = r; best_gain = gain; }
    }
    // interior vertices (no edge into any other part) are not worth
    // queueing: their best gain is -row[pv], a pure-loss move
    bool boundary = false;
    for (int32_t r = 0; r < W; ++r)
      if (r != pv && row[r] > 0) { boundary = true; break; }
    if (!boundary || best == pv) { *out_r = pv; return INT64_MIN; }
    *out_r = best;
    return best_gain;
  };

  // move v from pv to tgt, updating part/pw/conn rows of neighbors
  auto apply_move = [&](int64_t v, int32_t pv, int32_t tgt) {
    pw[pv] -= g.vw[v];
    pw[tgt] += g.vw[v];
    part[v] = tgt;
    for (int64_t k = g.indptr[v]; k < g.indptr[v + 1]; ++k) {
      int64_t* row = conn.data() + size_t(g.adj[k]) * W;
      row[pv] -= g.ew[k];
      row[tgt] += g.ew[k];
    }
  };

  struct Move { int64_t v; int32_t from, to; };
  std::vector<Move> trail;
  std::priority_queue<std::pair<int64_t, int64_t>> heap;  // (gain, v)

  for (int p = 0; p < passes; ++p) {
    std::fill(locked.begin(), locked.end(), 0);
    std::fill(cur_gain.begin(), cur_gain.end(), INT64_MIN);
    while (!heap.empty()) heap.pop();
    for (int64_t v = 0; v < g.nv; ++v) {
      int32_t tgt;
      const int64_t gain = best_from_row(v, &tgt);
      if (gain != INT64_MIN) { cur_gain[v] = gain; heap.emplace(gain, v); }
    }
    trail.clear();
    int64_t cum = 0, best_cum = 0;
    size_t best_len = 0;
    // stall cap (the classic FM early-out): once this many moves have
    // accumulated past the best prefix without improving it, the pass's
    // tail is already guaranteed rollback work — on power-law graphs the
    // uncapped tail is ~nv moves and dominates runtime while contributing
    // exactly nothing
    const size_t stall_cap =
        std::max<size_t>(1024, static_cast<size_t>(g.nv / 64));
    while (!heap.empty()) {
      if (trail.size() - best_len > stall_cap) break;
      auto [gain, v] = heap.top();
      heap.pop();
      if (locked[v] || gain != cur_gain[v]) continue;  // stale entry
      int32_t tgt;
      const int64_t now = best_from_row(v, &tgt);  // pw may have shifted
      if (now == INT64_MIN) { cur_gain[v] = INT64_MIN; continue; }
      if (now != gain) { cur_gain[v] = now; heap.emplace(now, v); continue; }
      const int32_t pv = part[v];
      apply_move(v, pv, tgt);
      locked[v] = 1;
      trail.push_back({v, pv, tgt});
      cum += now;
      if (cum > best_cum) { best_cum = cum; best_len = trail.size(); }
      // neighbors' rows changed by apply_move; refresh their queue keys
      for (int64_t k = g.indptr[v]; k < g.indptr[v + 1]; ++k) {
        const int64_t n = g.adj[k];
        if (locked[n]) continue;
        int32_t ntgt;
        const int64_t ngain = best_from_row(n, &ntgt);
        if (ngain != cur_gain[n]) {
          cur_gain[n] = ngain;
          if (ngain != INT64_MIN) heap.emplace(ngain, n);
        }
      }
    }
    // roll back to the best prefix (classic FM: the tail of the pass was
    // exploration that didn't pay off)
    for (size_t i = trail.size(); i > best_len; --i) {
      const Move& m = trail[i - 1];
      apply_move(m.v, m.to, m.from);
    }
    if (best_cum <= 0) break;  // pass found no net improvement
  }
}

// Communication-VOLUME polish: greedy positive-gain passes on the deduped
// halo-slot objective — the number of distinct (needing-rank, vertex)
// pairs, which is what actually sizes the halo all_to_all. FM above
// minimizes raw edge cut; on hub-heavy graphs the two diverge (a hub with
// 50 edges into rank r is 50 cut edges but ONE halo slot), so a final
// polish on the true wire metric recovers bytes the cut objective can't
// see. Gain of moving v from pv to tgt:
//   Δslots = [v needed by tgt before]        (that need disappears)
//          - [v needed by pv after]          (a new need appears)
//          + Σ_u∈N(v) ( [v was u's only pv-edge && owner(u)!=pv]
//                     - [u had no tgt-edge   && owner(u)!=tgt] )
// computed exactly from the same incremental [nv, W] connection table.
void volume_polish_impl(const WGraph& g, int32_t W,
                        std::vector<int32_t>& part, int passes, int64_t cap,
                        std::vector<int64_t>& pw,
                        std::vector<int64_t>& conn) {

  for (int p = 0; p < passes; ++p) {
    int64_t moves = 0;
    for (int64_t v = 0; v < g.nv; ++v) {
      const int32_t pv = part[v];
      const int64_t* row = conn.data() + size_t(v) * W;
      // candidate targets: ranks v already has edges into (moving toward
      // a rank with no edges can never reduce slots)
      int32_t best = pv;
      int64_t best_gain = 0, best_cut = 0;
      // the pv-side terms are target-independent: hoist them out of the
      // candidate loop (they're half the dominant inner-loop cost)
      int64_t pv_gain = row[pv] > 0 ? 0 : 1;  // tgt's need for v always
      // disappears (+1); pv starts needing v unless v has no pv edge
      for (int64_t k = g.indptr[v]; k < g.indptr[v + 1]; ++k) {
        const int64_t u = g.adj[k];
        if (conn[size_t(u) * W + pv] == g.ew[k] && part[u] != pv)
          pv_gain += 1;  // u stops being needed by pv (its only pv edge)
      }
      for (int32_t tgt = 0; tgt < W; ++tgt) {
        if (tgt == pv || row[tgt] == 0 || pw[tgt] + g.vw[v] > cap) continue;
        int64_t gain = pv_gain;
        for (int64_t k = g.indptr[v]; k < g.indptr[v + 1]; ++k) {
          const int64_t u = g.adj[k];
          if (conn[size_t(u) * W + tgt] == 0 && part[u] != tgt)
            gain -= 1;  // u becomes needed by tgt
        }
        const int64_t cut_gain = row[tgt] - row[pv];
        if (gain > best_gain ||
            (gain == best_gain && gain > 0 && cut_gain > best_cut)) {
          best = tgt;
          best_gain = gain;
          best_cut = cut_gain;
        }
      }
      if (best != pv && best_gain > 0) {
        pw[pv] -= g.vw[v];
        pw[best] += g.vw[v];
        part[v] = best;
        for (int64_t k = g.indptr[v]; k < g.indptr[v + 1]; ++k) {
          int64_t* urow = conn.data() + size_t(g.adj[k]) * W;
          urow[pv] -= g.ew[k];
          urow[best] += g.ew[k];
        }
        ++moves;
      }
    }
    if (!moves) break;
  }
}


// Public wrappers: env kill switches + the shared table build. The conn
// table is maintained incrementally across passes AND across rollbacks
// (apply/revert are the same table update with roles swapped), so one
// build serves FM and the volume polish back-to-back — at the finest
// level of a papers-fraction graph that's a multi-GB transient and an
// O(E) scan paid once instead of twice. Gate default 6 GB skips the
// papers100M finest level at W=8 (7.1 GB table); FM always runs on the
// coarser levels either way.
bool fm_enabled() {
  const char* env = std::getenv("DGRAPH_HOST_FM");
  return !(env && env[0] == '0');  // '0' = greedy-only A/B baseline
}

bool polish_enabled() {
  const char* env = std::getenv("DGRAPH_HOST_VOLUME_POLISH");
  if (env && env[0] == '0') return false;  // A/B kill switch
  // DGRAPH_HOST_FM=0 must yield the documented greedy-only baseline —
  // the polish counts as refinement
  return fm_enabled();
}

void fm_refine(const WGraph& g, int32_t world_size, std::vector<int32_t>& part,
               int passes, double imbalance) {
  if (!fm_enabled()) return;
  int64_t cap;
  std::vector<int64_t> pw, conn;
  if (!build_conn_table(g, world_size, part, imbalance, &cap, pw, conn))
    return;
  fm_refine_impl(g, world_size, part, passes, cap, pw, conn);
}

void fm_refine_and_polish(const WGraph& g, int32_t world_size,
                          std::vector<int32_t>& part, int fm_passes,
                          int polish_passes, double imbalance) {
  if (!fm_enabled()) return;
  int64_t cap;
  std::vector<int64_t> pw, conn;
  if (!build_conn_table(g, world_size, part, imbalance, &cap, pw, conn))
    return;
  fm_refine_impl(g, world_size, part, fm_passes, cap, pw, conn);
  if (polish_enabled())
    volume_polish_impl(g, world_size, part, polish_passes, cap, pw, conn);
}

// Multilevel body shared by the unweighted (raw edge list) and weighted
// (pre-coarsened) entries: coarsen by heavy-edge matching, partition the
// coarsest graph, project back with boundary refinement at every level.
void multilevel_core(WGraph&& g0, int32_t world_size, uint64_t seed,
                     int32_t* out_part) {
  const int64_t num_vertices = g0.nv;
  std::mt19937_64 rng(seed);
  std::vector<WGraph> levels;
  std::vector<std::vector<int64_t>> cmaps;
  levels.push_back(std::move(g0));
  // coarsen until ~16 coarse vertices per partition: deep enough that
  // locality clusters contract to single vertices (the initial partition
  // then only cuts inter-cluster links), shallow enough to stay balanced
  const int64_t coarse_target =
      std::max<int64_t>(static_cast<int64_t>(world_size) * 16, 64);
  int64_t total_vw = 0;
  for (auto w : levels[0].vw) total_vw += w;
  // supernode weight bound: 2x the average coarsest-level weight. Region
  // growth overshoots its cap by at most one vertex, so bounding vertex
  // weight bounds the initial imbalance at ~2/coarse_target (~1.6% at
  // W=8); rebalance_to_cap then enforces the 1.03 contract exactly.
  const int64_t max_vw = std::max<int64_t>(2 * total_vw / coarse_target, 1);
  while (levels.back().nv > coarse_target) {
    std::vector<int64_t> cmap;
    int64_t nc = heavy_edge_matching(levels.back(), rng, cmap, max_vw);
    if (nc > levels.back().nv * 95 / 100) break;  // matching stalled
    WGraph coarse = contract(levels.back(), cmap, nc);
    cmaps.push_back(std::move(cmap));
    levels.push_back(std::move(coarse));
  }
  std::vector<int32_t> part;
  initial_partition(levels.back(), world_size, rng, part);
  rebalance_to_cap(levels.back(), world_size, part, /*imbalance=*/1.03);
  // cheap greedy warmup, then hill-climbing FM (rollback makes the
  // negative-gain exploration safe at every level)
  refine(levels.back(), world_size, part, /*passes=*/4, /*imbalance=*/1.03);
  if (cmaps.empty()) {
    // no coarsening happened: the coarsest level IS the finest — run the
    // combined FM + volume polish here (the uncoarsening loop below won't)
    fm_refine_and_polish(levels[0], world_size, part, /*fm_passes=*/6,
                         /*polish_passes=*/4, /*imbalance=*/1.03);
  } else {
    fm_refine(levels.back(), world_size, part, /*passes=*/6,
              /*imbalance=*/1.03);
  }
  for (int64_t l = static_cast<int64_t>(cmaps.size()) - 1; l >= 0; --l) {
    const std::vector<int64_t>& cmap = cmaps[l];
    std::vector<int32_t> fine(levels[l].nv);
    for (int64_t v = 0; v < levels[l].nv; ++v) fine[v] = part[cmap[v]];
    part = std::move(fine);
    // greedy passes stay at the r3 value so DGRAPH_HOST_FM=0 reproduces
    // the pre-FM partitioner exactly (the A/B must isolate fm_refine)
    refine(levels[l], world_size, part, /*passes=*/2, /*imbalance=*/1.03);
    if (l == 0) {
      // finest level: FM + the halo-slot volume polish share ONE conn
      // table (the polish targets the metric that actually sizes the
      // padded all_to_all; only the finest level's slots ride the wire)
      fm_refine_and_polish(levels[0], world_size, part, /*fm_passes=*/3,
                           /*polish_passes=*/4, /*imbalance=*/1.03);
    } else {
      fm_refine(levels[l], world_size, part, /*passes=*/3,
                /*imbalance=*/1.03);
    }
  }
  std::memcpy(out_part, part.data(), num_vertices * sizeof(int32_t));
}

}  // namespace

// METIS-shaped multilevel k-way partition from a raw directed edge list.
void multilevel_partition(const int64_t* src, const int64_t* dst,
                          int64_t num_edges, int64_t num_vertices,
                          int32_t world_size, uint64_t seed,
                          int32_t* out_part) {
  multilevel_core(build_wgraph(src, dst, num_edges, num_vertices), world_size,
                  seed, out_part);
}

extern "C" void multilevel_partition_c(const int64_t* src, const int64_t* dst,
                                       int64_t num_edges, int64_t num_vertices,
                                       int32_t world_size, uint64_t seed,
                                       int32_t* out_part) {
  multilevel_partition(src, dst, num_edges, num_vertices, world_size, seed,
                       out_part);
}

// Raw-edge-list entry with CALLER vertex weights: same multilevel body,
// balance objective Σ vw per rank. The full-scale papers100M record
// showed why this exists: vertex-balanced partitions leave the EDGE
// distribution 1.28x imbalanced (e_pad 257.6M vs the 201M/rank mean,
// logs/p100m_fullscale_r5.jsonl), and e_pad sizes the dominant runtime
// edge buffers; vw = 1 + alpha*degree trades a little vertex padding for
// edge balance.
extern "C" void multilevel_partition_vw_c(
    const int64_t* src, const int64_t* dst, int64_t num_edges,
    const int64_t* vw, int64_t num_vertices, int32_t world_size,
    uint64_t seed, int32_t* out_part) {
  WGraph g = build_wgraph(src, dst, num_edges, num_vertices);
  g.vw.assign(vw, vw + num_vertices);
  multilevel_core(std::move(g), world_size, seed, out_part);
}

// Weighted entry: unique undirected pairs + weights + vertex weights (the
// chunked contraction's output). The balance objective is Σ vw per rank,
// so a partition of cluster-coarsened supernodes stays balanced in FINE
// vertices after projection.
extern "C" void multilevel_partition_w_c(
    const int64_t* usrc, const int64_t* udst, const int64_t* uw,
    int64_t num_pairs, const int64_t* vw, int64_t num_vertices,
    int32_t world_size, uint64_t seed, int32_t* out_part) {
  multilevel_core(
      build_wgraph_weighted(usrc, udst, uw, num_pairs, vw, num_vertices),
      world_size, seed, out_part);
}

namespace {

// Symmetrized int32 CSR (4 bytes x 2E adjacency, parallel edges kept —
// dedup would need a per-vertex sort; a multiplicity-2 neighbor just gets
// scanned twice). Shared by the memory-bounded partition entry points.
// Returns false when vertex ids would not fit int32 — callers must fail
// fast rather than wrap ids negative.
bool build_csr32(const int64_t* src, const int64_t* dst, int64_t num_edges,
                 int64_t num_vertices, std::vector<int64_t>& indptr,
                 std::vector<int32_t>& adj) {
  if (num_vertices >= INT32_MAX) return false;
  indptr.assign(num_vertices + 1, 0);
  {
    // per-vertex degree <= 2E < 2^32 needs int64 only if one vertex
    // touches >2^31 edges; ids are the int32-bound quantity here
    std::vector<int64_t> deg(num_vertices, 0);
    for (int64_t e = 0; e < num_edges; ++e) {
      if (src[e] == dst[e]) continue;
      ++deg[src[e]];
      ++deg[dst[e]];
    }
    for (int64_t v = 0; v < num_vertices; ++v)
      indptr[v + 1] = indptr[v] + deg[v];
  }
  adj.assign(indptr[num_vertices], 0);
  std::vector<int64_t> cur(indptr.begin(), indptr.end() - 1);
  for (int64_t e = 0; e < num_edges; ++e) {
    if (src[e] == dst[e]) continue;
    adj[cur[src[e]]++] = static_cast<int32_t>(dst[e]);
    adj[cur[dst[e]]++] = static_cast<int32_t>(src[e]);
  }
  return true;
}

// Force every rank under cap on an int32 CSR — the CSR-form sibling of
// rebalance_to_cap (same policy: shed over-cap ranks to the
// best-connected under-cap rank, tie-break most underfull; keep the two
// in lock-step when changing the heuristic). vw == nullptr means unit
// vertex weights; otherwise the cap is on Σ vw (edge-balance blends).
void rebalance_csr32(const std::vector<int64_t>& indptr,
                     const std::vector<int32_t>& adj, int64_t num_vertices,
                     int32_t W, int64_t cap, const int64_t* vw,
                     int32_t* part, std::vector<int64_t>& pw) {
  std::vector<int64_t> conn(W, 0);
  for (int sweep = 0; sweep < 8; ++sweep) {
    bool over = false;
    for (int32_t r = 0; r < W; ++r) over |= pw[r] > cap;
    if (!over) return;
    bool moved = false;
    for (int64_t v = 0; v < num_vertices; ++v) {
      const int32_t pv = part[v];
      if (pw[pv] <= cap) continue;
      const int64_t w = vw ? vw[v] : 1;
      std::fill(conn.begin(), conn.end(), 0);
      for (int64_t k = indptr[v]; k < indptr[v + 1]; ++k)
        ++conn[part[adj[k]]];
      int32_t best = -1;
      int64_t best_conn = -1, best_pw = INT64_MAX;
      for (int32_t r = 0; r < W; ++r) {
        if (r == pv || pw[r] + w > cap) continue;
        if (conn[r] > best_conn ||
            (conn[r] == best_conn && pw[r] < best_pw)) {
          best = r;
          best_conn = conn[r];
          best_pw = pw[r];
        }
      }
      if (best >= 0) {
        pw[pv] -= w;
        pw[best] += w;
        part[v] = best;
        moved = true;
      }
    }
    if (!moved) return;
  }
}

}  // namespace

// Capped greedy cluster coarsening for graphs whose in-RAM WGraph stack
// would blow the host (VERDICT r4 #6: 22M nodes -> 104 GB RSS; 111M is
// 5x out of reach). Memory here is ONE int32 CSR (4 bytes x 2E) + O(V)
// int64 arrays — ~18 GB at full papers100M against the WGraph path's
// >250 GB. Degree-ascending visiting (random within a degree class) lets
// cluster-interior vertices seed clusters before hubs can swallow
// cross-cluster neighborhoods — the same ordering rationale as
// heavy_edge_matching above. A second sweep merges the singleton clusters
// the greedy pass strands (hubs visited last find their neighbors taken).
// Returns the number of clusters (-1: ids would not fit int32);
// out_cmap[v] = cluster id.
extern "C" int64_t cluster_coarsen_c(const int64_t* src, const int64_t* dst,
                                     int64_t num_edges, int64_t num_vertices,
                                     int64_t max_cluster_weight, uint64_t seed,
                                     int64_t* out_cmap) {
  std::vector<int64_t> indptr;
  std::vector<int32_t> adj;
  if (!build_csr32(src, dst, num_edges, num_vertices, indptr, adj)) return -1;
  std::vector<int64_t> order(num_vertices);
  for (int64_t i = 0; i < num_vertices; ++i) order[i] = i;
  std::mt19937_64 rng(seed);
  std::shuffle(order.begin(), order.end(), rng);
  std::stable_sort(order.begin(), order.end(), [&](int64_t a, int64_t b) {
    return (indptr[a + 1] - indptr[a]) < (indptr[b + 1] - indptr[b]);
  });

  std::fill(out_cmap, out_cmap + num_vertices, int64_t(-1));
  std::vector<int64_t> cw;  // cluster weights
  cw.reserve(num_vertices / std::max<int64_t>(max_cluster_weight / 2, 1) + 16);
  int64_t nc = 0;
  // one-ring absorption, deliberately: a capped-BFS region-growth variant
  // was measured WORSE (2M power-law, W=8: cut 0.770 vs 0.757 at mcw=4 —
  // blob atoms are too coarse for the downstream FM), and deeper
  // coarsening cannot shrink the coarse EDGE count anyway (pairs stayed
  // ~0.93E even at 16x vertex reduction; hub-adjacent edges never merge)
  for (int64_t i = 0; i < num_vertices; ++i) {
    const int64_t v = order[i];
    if (out_cmap[v] >= 0) continue;
    const int64_t c = nc++;
    out_cmap[v] = c;
    int64_t w = 1;
    for (int64_t k = indptr[v]; k < indptr[v + 1] && w < max_cluster_weight;
         ++k) {
      const int32_t n = adj[k];
      if (out_cmap[n] < 0) {
        out_cmap[n] = c;
        ++w;
      }
    }
    cw.push_back(w);
  }
  // singleton-merge sweep: a stranded singleton joins the first neighbor
  // cluster with room (fragmented clusters inflate the coarse graph and
  // starve the initial partition of contiguous regions)
  for (int64_t i = 0; i < num_vertices; ++i) {
    const int64_t v = order[i];
    const int64_t c = out_cmap[v];
    if (cw[c] != 1) continue;
    for (int64_t k = indptr[v]; k < indptr[v + 1]; ++k) {
      const int64_t cn = out_cmap[adj[k]];
      if (cn != c && cw[cn] < max_cluster_weight) {
        out_cmap[v] = cn;
        ++cw[cn];
        --cw[c];
        break;
      }
    }
  }
  // compact away the emptied cluster ids so the coarse graph is dense
  std::vector<int64_t> remap(nc, -1);
  int64_t dense = 0;
  for (int64_t v = 0; v < num_vertices; ++v) {
    int64_t& c = out_cmap[v];
    if (remap[c] < 0) remap[c] = dense++;
    c = remap[c];
  }
  return dense;
}

// Greedy positive-gain boundary refinement on the FINE graph after
// projection, one int32 CSR — the memory-bounded counterpart of refine()
// for graphs whose WGraph doesn't fit. O(E) per pass (boundary check +
// conn scan are both neighbor scans). The cut GAIN is always unit edge
// counts; vw (nullable) only changes what the balance cap sums — the
// edge-balance blend must use the same vw here as in the coarse stage,
// or this refine's rebalance undoes the blend (measured: e_imb 1.14
// pre-refine -> 1.25 after a unit-count refine at 2M power-law).
// Returns 0 on success, -1 when build_csr32 refuses (vertex ids would
// not fit int32) — mirroring cluster_coarsen_c's -1 so non-Python
// callers cannot mistake a silent no-op for a refined partition
// (ADVICE r5; the Python wrappers additionally pre-check the bound).
namespace {
int32_t refine_csr_impl(const int64_t* src, const int64_t* dst,
                        int64_t num_edges, int64_t num_vertices, int32_t W,
                        int32_t passes, double imbalance, const int64_t* vw,
                        int32_t* part) {
  std::vector<int64_t> indptr;
  std::vector<int32_t> adj;
  if (!build_csr32(src, dst, num_edges, num_vertices, indptr, adj))
    return -1;
  int64_t total_w = 0;
  if (vw) {
    for (int64_t v = 0; v < num_vertices; ++v) total_w += vw[v];
  } else {
    total_w = num_vertices;
  }
  const int64_t cap =
      static_cast<int64_t>((double(total_w) / W) * imbalance) + 1;
  std::vector<int64_t> pw(W, 0);
  for (int64_t v = 0; v < num_vertices; ++v) pw[part[v]] += vw ? vw[v] : 1;
  // rebalance first: an over-cap input (e.g. a projected partition built
  // under different weights) can never be fixed by gain-driven passes —
  // they only refuse to create new violations
  rebalance_csr32(indptr, adj, num_vertices, W, cap, vw, part, pw);
  std::vector<int64_t> conn(W, 0);
  for (int32_t p = 0; p < passes; ++p) {
    int64_t moves = 0;
    for (int64_t v = 0; v < num_vertices; ++v) {
      const int32_t pv = part[v];
      bool boundary = false;
      for (int64_t k = indptr[v]; k < indptr[v + 1]; ++k)
        if (part[adj[k]] != pv) { boundary = true; break; }
      if (!boundary) continue;
      const int64_t w = vw ? vw[v] : 1;
      std::fill(conn.begin(), conn.end(), 0);
      for (int64_t k = indptr[v]; k < indptr[v + 1]; ++k)
        ++conn[part[adj[k]]];
      int32_t best = pv;
      int64_t best_gain = 0;
      for (int32_t r = 0; r < W; ++r) {
        if (r == pv || pw[r] + w > cap) continue;
        const int64_t gain = conn[r] - conn[pv];
        if (gain > best_gain) { best = r; best_gain = gain; }
      }
      if (best != pv) {
        pw[pv] -= w;
        pw[best] += w;
        part[v] = best;
        ++moves;
      }
    }
    if (!moves) break;
  }
  return 0;
}
}  // namespace

extern "C" int32_t refine_unweighted_csr_c(const int64_t* src,
                                           const int64_t* dst,
                                           int64_t num_edges,
                                           int64_t num_vertices, int32_t W,
                                           int32_t passes, double imbalance,
                                           int32_t* part) {
  return refine_csr_impl(src, dst, num_edges, num_vertices, W, passes,
                         imbalance, nullptr, part);
}

extern "C" int32_t refine_weighted_csr_c(const int64_t* src,
                                         const int64_t* dst,
                                         int64_t num_edges,
                                         int64_t num_vertices, int32_t W,
                                         int32_t passes, double imbalance,
                                         const int64_t* vw, int32_t* part) {
  return refine_csr_impl(src, dst, num_edges, num_vertices, W, passes,
                         imbalance, vw, part);
}

// Deduplicate (key, value) pairs encoded as key*stride+value, sorted.
// Returns the number of unique pairs written to out (caller allocates n).
int64_t unique_encoded_pairs(const int64_t* keys, const int64_t* vals,
                             int64_t n, int64_t stride, int64_t* out) {
  std::vector<int64_t> enc(n);
  for (int64_t i = 0; i < n; ++i) enc[i] = keys[i] * stride + vals[i];
  std::sort(enc.begin(), enc.end());
  auto end = std::unique(enc.begin(), enc.end());
  int64_t m = static_cast<int64_t>(end - enc.begin());
  std::memcpy(out, enc.data(), m * sizeof(int64_t));
  return m;
}

// ---------------------------------------------------------------------------
// Streaming edge-plan core for billion-edge graphs (SURVEY §7 "papers100M
// plan build"; the reference precomputes per-rank plans offline and caches
// them to disk for MAG240M, MAG240M_dataset.py:237-260).
//
// The numpy builder (dgraph_tpu/plan.py build_edge_plan) lexsorts and
// np.uniques over all E edges with ~10 int64 temporaries — at E=1.6e9
// that's >100 GB of transients on this single-core host. This core does
// the same computation with counting/radix sorts and bounded buffers:
//   1. owner rank per edge + counting sort by owner,
//   2. per-rank LSD radix sort by owner-side local vertex id (monotone
//      segment ids for the sorted-scatter kernels),
//   3. cross-edge (needer, halo-vid) pair sort + run-length dedup, with
//      halo-slot ids propagated back to edges during the scan (no
//      binary-search pass),
//   4. direct fill of the padded [W, E_pad] / [W, W, S_pad] plan arrays.
// Two-call protocol: begin() computes sizes (caller picks padding and
// allocates numpy outputs), fill() writes them, free() drops the context.
// ---------------------------------------------------------------------------

namespace {

struct PlanCtx {
  int64_t E = 0;
  int32_t W = 0;
  int edge_owner_dst = 1;
  std::vector<int32_t> owner;      // [E]
  std::vector<int64_t> e_counts;   // [W]
  std::vector<int32_t> edge_slot;  // [E] slot within owner rank (sorted order)
  std::vector<int64_t> halo_counts;  // [W*W] (sender, needer)
  std::vector<int32_t> edge_pair;  // [E] unique-pair id per cross edge, -1 local
  // per unique (needer, vid) pair, sorted by (needer, vid):
  std::vector<int64_t> pair_vid;
  std::vector<int32_t> pair_needer, pair_sender, pair_pos;
};

// LSD radix sort of (key, val) arrays by key, 8 bits per pass.
void radix_sort_u64(std::vector<uint64_t>& keys, std::vector<uint32_t>& vals,
                    uint64_t max_key) {
  int passes = 0;
  while (max_key >> (8 * passes)) ++passes;
  if (passes == 0) passes = 1;
  size_t n = keys.size();
  std::vector<uint64_t> kbuf(n);
  std::vector<uint32_t> vbuf(n);
  for (int p = 0; p < passes; ++p) {
    size_t count[257] = {0};
    int shift = 8 * p;
    for (size_t i = 0; i < n; ++i) ++count[((keys[i] >> shift) & 0xff) + 1];
    for (int b = 0; b < 256; ++b) count[b + 1] += count[b];
    for (size_t i = 0; i < n; ++i) {
      size_t pos = count[(keys[i] >> shift) & 0xff]++;
      kbuf[pos] = keys[i];
      vbuf[pos] = vals[i];
    }
    keys.swap(kbuf);
    vals.swap(vbuf);
  }
}

}  // namespace

// Phase 1: sort + halo analysis. Returns an opaque context; writes
// out_sizes = {max per-rank edge count, max per-(sender,needer) halo count,
// unique halo pairs, cross edge count}.
void* plan_core_begin(const int64_t* src, const int64_t* dst, int64_t E,
                      const int32_t* src_part, const int32_t* dst_part,
                      const int64_t* src_offsets, const int64_t* dst_offsets,
                      int64_t v_src, int64_t v_dst, int32_t W,
                      int32_t edge_owner_dst, int64_t* out_sizes) {
  // edge ids, per-rank slots, and pair ids are all stored in 32-bit
  // fields; the signed ones (edge_slot, edge_pair) wrap at 2^31 — refuse
  // anything that could overflow instead of silently corrupting the plan
  if (E >= (int64_t(1) << 31)) return nullptr;
  auto* ctx = new PlanCtx();
  ctx->E = E;
  ctx->W = W;
  ctx->edge_owner_dst = edge_owner_dst;
  const int64_t* owner_vid = edge_owner_dst ? dst : src;
  const int64_t* halo_vid = edge_owner_dst ? src : dst;
  const int32_t* owner_part = edge_owner_dst ? dst_part : src_part;
  const int32_t* halo_part = edge_owner_dst ? src_part : dst_part;
  const int64_t* owner_off = edge_owner_dst ? dst_offsets : src_offsets;

  // 1. owner rank per edge + counts
  ctx->owner.resize(E);
  ctx->e_counts.assign(W, 0);
  for (int64_t e = 0; e < E; ++e) {
    int32_t r = owner_part[owner_vid[e]];
    ctx->owner[e] = r;
    ++ctx->e_counts[r];
  }

  // 2. stable counting sort by owner, then per-rank radix by local owner vid
  std::vector<int64_t> rank_start(W + 1, 0);
  for (int32_t r = 0; r < W; ++r) rank_start[r + 1] = rank_start[r] + ctx->e_counts[r];
  ctx->edge_slot.resize(E);
  {
    std::vector<int64_t> cur(rank_start.begin(), rank_start.end() - 1);
    // bucket pass: per-rank (local_vid, orig_idx) entries
    std::vector<uint64_t> bkeys(E);
    std::vector<uint32_t> bvals(E);
    for (int64_t e = 0; e < E; ++e) {
      int32_t r = ctx->owner[e];
      int64_t pos = cur[r]++;
      bkeys[pos] = static_cast<uint64_t>(owner_vid[e] - owner_off[r]);
      bvals[pos] = static_cast<uint32_t>(e);
    }
    for (int32_t r = 0; r < W; ++r) {
      int64_t lo = rank_start[r], n = ctx->e_counts[r];
      if (n == 0) continue;
      uint64_t max_local = 0;
      for (int64_t i = lo; i < lo + n; ++i) max_local = std::max(max_local, bkeys[i]);
      std::vector<uint64_t> k(bkeys.begin() + lo, bkeys.begin() + lo + n);
      std::vector<uint32_t> v(bvals.begin() + lo, bvals.begin() + lo + n);
      radix_sort_u64(k, v, max_local);
      for (int64_t i = 0; i < n; ++i) ctx->edge_slot[v[i]] = static_cast<int32_t>(i);
    }
  }

  // 3. cross-pair dedup with slot propagation; bucket by needer (= owner)
  // first so the per-bucket radix ping-pong buffers are ~1/W of n_cross
  // (a full-width sort's transient is ~24 bytes/cross-edge — tens of GB
  // at papers100M scale)
  std::vector<int64_t> nc_counts(W, 0);
  for (int64_t e = 0; e < E; ++e)
    if (halo_part[halo_vid[e]] != ctx->owner[e]) ++nc_counts[ctx->owner[e]];
  std::vector<int64_t> nc_start(W + 1, 0);
  for (int32_t r = 0; r < W; ++r) nc_start[r + 1] = nc_start[r] + nc_counts[r];
  const int64_t n_cross = nc_start[W];
  ctx->edge_pair.assign(E, -1);
  ctx->halo_counts.assign(static_cast<size_t>(W) * W, 0);
  int64_t v_halo = edge_owner_dst ? v_src : v_dst;
  const int64_t* halo_off = edge_owner_dst ? src_offsets : dst_offsets;
  if (n_cross > 0) {
    std::vector<uint64_t> keys(n_cross);
    std::vector<uint32_t> vals(n_cross);
    {
      std::vector<int64_t> cur(nc_start.begin(), nc_start.end() - 1);
      for (int64_t e = 0; e < E; ++e) {
        int64_t hv = halo_vid[e];
        int32_t r = ctx->owner[e];
        if (halo_part[hv] != r) {
          int64_t pos = cur[r]++;
          keys[pos] = static_cast<uint64_t>(hv);
          vals[pos] = static_cast<uint32_t>(e);
        }
      }
    }
    for (int32_t r = 0; r < W; ++r) {
      int64_t lo = nc_start[r], n = nc_counts[r];
      if (n == 0) continue;
      std::vector<uint64_t> k(keys.begin() + lo, keys.begin() + lo + n);
      std::vector<uint32_t> v(vals.begin() + lo, vals.begin() + lo + n);
      radix_sort_u64(k, v, static_cast<uint64_t>(v_halo));
      std::copy(k.begin(), k.end(), keys.begin() + lo);
      std::copy(v.begin(), v.end(), vals.begin() + lo);
    }
    // re-encode to global (needer, vid) keys for the run-length scan
    for (int32_t r = 0; r < W; ++r)
      for (int64_t i = nc_start[r]; i < nc_start[r + 1]; ++i)
        keys[i] += static_cast<uint64_t>(r) * v_halo;
    // exact reserve (push_back doubling would spike ~2x at H ~ 1e8+)
    int64_t H_total = n_cross > 0 ? 1 : 0;
    for (int64_t i = 1; i < n_cross; ++i) H_total += keys[i] != keys[i - 1];
    ctx->pair_vid.reserve(H_total);
    ctx->pair_needer.reserve(H_total);
    ctx->pair_sender.reserve(H_total);
    ctx->pair_pos.reserve(H_total);
    // run-length scan: assign pair ids; pos within (needer, sender) run
    int64_t H = 0;
    int32_t run_needer = -1, run_sender = -1, pos = 0;
    uint64_t prev_key = ~0ull;
    for (int64_t i = 0; i < n_cross; ++i) {
      if (keys[i] != prev_key) {
        prev_key = keys[i];
        int32_t needer = static_cast<int32_t>(keys[i] / v_halo);
        int64_t vid = static_cast<int64_t>(keys[i] % v_halo);
        int32_t sender = halo_part[vid];
        if (needer != run_needer || sender != run_sender) {
          run_needer = needer;
          run_sender = sender;
          pos = 0;
        }
        ctx->pair_vid.push_back(vid);
        ctx->pair_needer.push_back(needer);
        ctx->pair_sender.push_back(sender);
        ctx->pair_pos.push_back(pos++);
        ++ctx->halo_counts[static_cast<size_t>(sender) * W + needer];
        ++H;
      }
      ctx->edge_pair[vals[i]] = static_cast<int32_t>(H - 1);
    }
    (void)halo_off;
  }

  int64_t e_max = 0, s_max = 0;
  for (int32_t r = 0; r < W; ++r) e_max = std::max(e_max, ctx->e_counts[r]);
  for (auto c : ctx->halo_counts) s_max = std::max(s_max, c);
  out_sizes[0] = e_max;
  out_sizes[1] = s_max;
  out_sizes[2] = static_cast<int64_t>(ctx->pair_vid.size());
  out_sizes[3] = n_cross;
  return ctx;
}

// Phase 2: fill the padded plan arrays (preallocated by the caller).
void plan_core_fill(void* ctx_, const int64_t* src, const int64_t* dst,
                    const int64_t* src_offsets, const int64_t* dst_offsets,
                    int64_t e_pad, int64_t s_pad, int64_t n_owner_pad,
                    int64_t n_halo_pad, int32_t* src_index, int32_t* dst_index,
                    float* edge_mask, int32_t* send_idx, float* send_mask,
                    int64_t* halo_counts_out, int32_t* edge_rank_out,
                    int64_t* edge_slot_out) {
  auto* ctx = static_cast<PlanCtx*>(ctx_);
  const int64_t E = ctx->E;
  const int32_t W = ctx->W;
  const int64_t* owner_vid = ctx->edge_owner_dst ? dst : src;
  const int64_t* halo_vid = ctx->edge_owner_dst ? src : dst;
  const int64_t* owner_off = ctx->edge_owner_dst ? dst_offsets : src_offsets;
  const int64_t* halo_off = ctx->edge_owner_dst ? src_offsets : dst_offsets;
  int32_t* owner_index = ctx->edge_owner_dst ? dst_index : src_index;
  int32_t* halo_index = ctx->edge_owner_dst ? src_index : dst_index;

  // padding conventions (plan.py build_edge_plan): owner-side padded slots
  // carry n_owner_pad (monotone tail, dropped by segment reductions);
  // halo-side and send arrays carry 0 with mask 0 (the halo-SORTED route
  // does not sort that 0 into block 0: plan.py halo_sort_route keys masked
  // edges past the last vertex block, see EdgePlan.halo_sorted_ids)
  std::fill(owner_index, owner_index + static_cast<size_t>(W) * e_pad,
            static_cast<int32_t>(n_owner_pad));
  std::memset(halo_index, 0, static_cast<size_t>(W) * e_pad * sizeof(int32_t));
  std::memset(edge_mask, 0, static_cast<size_t>(W) * e_pad * sizeof(float));
  std::memset(send_idx, 0, static_cast<size_t>(W) * W * s_pad * sizeof(int32_t));
  std::memset(send_mask, 0, static_cast<size_t>(W) * W * s_pad * sizeof(float));

  for (int64_t e = 0; e < E; ++e) {
    int32_t r = ctx->owner[e];
    int64_t at = static_cast<int64_t>(r) * e_pad + ctx->edge_slot[e];
    owner_index[at] = static_cast<int32_t>(owner_vid[e] - owner_off[r]);
    int32_t p = ctx->edge_pair[e];
    if (p < 0) {
      halo_index[at] = static_cast<int32_t>(halo_vid[e] - halo_off[r]);
    } else {
      halo_index[at] = static_cast<int32_t>(
          n_halo_pad + static_cast<int64_t>(ctx->pair_sender[p]) * s_pad +
          ctx->pair_pos[p]);
    }
    edge_mask[at] = 1.0f;
    edge_rank_out[e] = r;
    edge_slot_out[e] = ctx->edge_slot[e];
  }

  for (size_t i = 0; i < ctx->pair_vid.size(); ++i) {
    int32_t s = ctx->pair_sender[i], n = ctx->pair_needer[i];
    int64_t at = (static_cast<int64_t>(s) * W + n) * s_pad + ctx->pair_pos[i];
    send_idx[at] = static_cast<int32_t>(ctx->pair_vid[i] - halo_off[s]);
    send_mask[at] = 1.0f;
  }
  std::memcpy(halo_counts_out, ctx->halo_counts.data(),
              static_cast<size_t>(W) * W * sizeof(int64_t));
}

void plan_core_free(void* ctx_) { delete static_cast<PlanCtx*>(ctx_); }

// Multi-threaded edge-cut count (partition quality metric at scale).
int64_t edge_cut_count(const int64_t* src, const int64_t* dst, int64_t num_edges,
                       const int32_t* part) {
  unsigned hw = std::thread::hardware_concurrency();
  int num_threads = hw ? static_cast<int>(hw) : 4;
  if (num_edges < (1 << 16)) num_threads = 1;
  std::vector<int64_t> partial(num_threads, 0);
  std::vector<std::thread> threads;
  int64_t chunk = (num_edges + num_threads - 1) / num_threads;
  for (int t = 0; t < num_threads; ++t) {
    threads.emplace_back([&, t]() {
      int64_t lo = t * chunk, hi = std::min<int64_t>(num_edges, lo + chunk);
      int64_t c = 0;
      for (int64_t e = lo; e < hi; ++e)
        if (part[src[e]] != part[dst[e]]) ++c;
      partial[t] = c;
    });
  }
  for (auto& th : threads) th.join();
  int64_t total = 0;
  for (auto c : partial) total += c;
  return total;
}

}  // extern "C"
