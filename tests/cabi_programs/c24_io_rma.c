/* MPI-IO views + individual pointers + ordered access; dynamic RMA
 * windows; Alltoallw. References:
 * ompi/mpi/c/file_set_view.c.in, file_iread.c.in,
 * file_read_ordered.c.in, win_create_dynamic.c.in, win_attach.c.in,
 * alltoallw.c.in. */
#include <mpi.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <unistd.h>

static int rank, size;

#define CHECK(cond, code)                                            \
    do {                                                             \
        if (!(cond)) {                                               \
            fprintf(stderr, "rank %d: check failed at line %d\n",    \
                    rank, __LINE__);                                 \
            MPI_Abort(MPI_COMM_WORLD, code);                         \
        }                                                            \
    } while (0)

int main(int argc, char **argv)
{
    MPI_Init(&argc, &argv);
    MPI_Comm_rank(MPI_COMM_WORLD, &rank);
    MPI_Comm_size(MPI_COMM_WORLD, &size);
    CHECK(size >= 2, 1);

    char path[256];
    snprintf(path, sizeof(path), "/tmp/ompi_tpu_c24_%d.bin",
             (int)getppid());

    /* ---- file views: strided filetype per rank ------------------ */
    {
        MPI_File fh;
        CHECK(MPI_File_open(MPI_COMM_WORLD, path,
                            MPI_MODE_CREATE | MPI_MODE_RDWR,
                            MPI_INFO_NULL, &fh) == MPI_SUCCESS, 2);
        /* view: ints, filetype = my 1 slot out of every `size` */
        MPI_Datatype ft;
        MPI_Type_vector(4, 1, size, MPI_INT, &ft);
        MPI_Datatype ftr;
        MPI_Type_create_resized(ft, 0, 4 * size * (int)sizeof(int),
                                &ftr);
        MPI_Type_commit(&ftr);
        CHECK(MPI_File_set_view(fh, (MPI_Offset)(rank * sizeof(int)),
                                MPI_INT, ftr, "native", MPI_INFO_NULL)
              == MPI_SUCCESS, 3);
        MPI_Datatype get_et = MPI_DATATYPE_NULL,
                     get_ft = MPI_DATATYPE_NULL;
        MPI_Offset get_disp = -1;
        char rep[32] = "";
        CHECK(MPI_File_get_view(fh, &get_disp, &get_et, &get_ft, rep)
              == MPI_SUCCESS, 4);
        CHECK(get_disp == (MPI_Offset)(rank * sizeof(int)), 5);
        CHECK(strcmp(rep, "native") == 0, 6);

        /* individual-pointer writes through the view: my 4 slots */
        int mine[4];
        for (int i = 0; i < 4; i++)
            mine[i] = 100 * rank + i;
        MPI_Status st;
        CHECK(MPI_File_write(fh, mine, 2, MPI_INT, &st)
              == MPI_SUCCESS, 7);
        MPI_Request r;
        CHECK(MPI_File_iwrite(fh, mine + 2, 2, MPI_INT, &r)
              == MPI_SUCCESS, 8);
        MPI_Wait(&r, &st);
        MPI_Offset pos = -1;
        CHECK(MPI_File_get_position(fh, &pos) == MPI_SUCCESS
              && pos == 4, 9);
        MPI_File_sync(fh);
        MPI_Barrier(MPI_COMM_WORLD);

        /* read back through the view from the start */
        CHECK(MPI_File_seek(fh, 0, MPI_SEEK_SET) == MPI_SUCCESS, 10);
        int back[4] = {0};
        CHECK(MPI_File_read(fh, back, 2, MPI_INT, &st) == MPI_SUCCESS,
              11);
        CHECK(MPI_File_iread(fh, back + 2, 2, MPI_INT, &r)
              == MPI_SUCCESS, 12);
        MPI_Wait(&r, &st);
        for (int i = 0; i < 4; i++)
            CHECK(back[i] == 100 * rank + i, 13);

        /* drop the view: raw bytes show the interleaving */
        CHECK(MPI_File_set_view(fh, 0, MPI_BYTE, MPI_BYTE, "native",
                                MPI_INFO_NULL) == MPI_SUCCESS, 14);
        int flat[8];
        CHECK(MPI_File_read_at(fh, 0, flat, 2 * size, MPI_INT, &st)
              == MPI_SUCCESS, 15);
        /* word j of round k belongs to rank j: value 100*j + k */
        for (int j = 0; j < size && j < 8; j++)
            CHECK(flat[j] == 100 * j, 16);
        MPI_Type_free(&ft);
        MPI_Type_free(&ftr);
        MPI_File_close(&fh);
    }

    /* ---- ordered (rank-sequential) shared-pointer access -------- */
    {
        MPI_File fh;
        char path2[256];
        snprintf(path2, sizeof(path2), "%s.ord", path);
        CHECK(MPI_File_open(MPI_COMM_WORLD, path2,
                            MPI_MODE_CREATE | MPI_MODE_RDWR,
                            MPI_INFO_NULL, &fh) == MPI_SUCCESS, 17);
        int two[2] = {10 * rank, 10 * rank + 1};
        MPI_Status st;
        CHECK(MPI_File_write_ordered(fh, two, 2, MPI_INT, &st)
              == MPI_SUCCESS, 18);
        MPI_File_sync(fh);
        MPI_Barrier(MPI_COMM_WORLD);
        /* every rank re-reads the whole file in rank order */
        MPI_Offset sz = -1;
        MPI_File_get_size(fh, &sz);
        CHECK(sz == (MPI_Offset)(2 * size * sizeof(int)), 19);
        MPI_Offset sp = -1;
        CHECK(MPI_File_get_position_shared(fh, &sp) == MPI_SUCCESS
              && sp == (MPI_Offset)(2 * size * sizeof(int)), 50);
        CHECK(MPI_File_seek_shared(fh, 0, MPI_SEEK_SET)
              == MPI_SUCCESS, 51);
        int got[2] = {-1, -1};
        CHECK(MPI_File_read_ordered(fh, got, 2, MPI_INT, &st)
              == MPI_SUCCESS, 20);
        CHECK(got[0] == 10 * rank && got[1] == 10 * rank + 1, 21);
        MPI_File_close(&fh);
        if (rank == 0)
            unlink(path2);
    }
    if (rank == 0)
        unlink(path);

    /* ---- dynamic window: attach my memory, peers PUT by address - */
    {
        MPI_Win win;
        CHECK(MPI_Win_create_dynamic(MPI_INFO_NULL, MPI_COMM_WORLD,
                                     &win) == MPI_SUCCESS, 22);
        double slab[8];
        for (int i = 0; i < 8; i++)
            slab[i] = -1.0;
        CHECK(MPI_Win_attach(win, slab, sizeof(slab)) == MPI_SUCCESS,
              23);
        /* publish my slab's address */
        MPI_Aint myaddr;
        CHECK(MPI_Get_address(slab, &myaddr) == MPI_SUCCESS, 24);
        MPI_Aint *addrs = malloc(size * sizeof(MPI_Aint));
        CHECK(MPI_Allgather(&myaddr, 1, MPI_AINT, addrs, 1, MPI_AINT,
                            MPI_COMM_WORLD) == MPI_SUCCESS, 25);

        MPI_Win_fence(0, win);
        /* everyone puts one double into the RIGHT neighbor's slab at
         * slot = my rank */
        int tgt = (rank + 1) % size;
        double v = 1000.0 + rank;
        CHECK(MPI_Put(&v, 1, MPI_DOUBLE, tgt,
                      addrs[tgt] + (MPI_Aint)(rank * sizeof(double)),
                      1, MPI_DOUBLE, win) == MPI_SUCCESS, 26);
        MPI_Win_fence(0, win);
        int left = (rank - 1 + size) % size;
        CHECK(slab[left] == 1000.0 + left, 27);
        /* untouched slots keep their memory */
        for (int i = 0; i < 8; i++)
            if (i != left)
                CHECK(slab[i] == -1.0, 28);
        CHECK(MPI_Win_detach(win, slab) == MPI_SUCCESS, 29);
        MPI_Win_free(&win);
        free(addrs);
    }

    /* ---- Alltoallw: per-peer types AND byte displacements ------- */
    {
        /* send to peer j: j+1 ints starting at byte 4*j*rank-ish —
         * keep it simple: contiguous lanes of varying count */
        int scount[16], rcount[16], sdisp[16], rdisp[16];
        MPI_Datatype stype[16], rtype[16];
        CHECK(size <= 16, 30);
        int stot = 0, rtot = 0;
        for (int j = 0; j < size; j++) {
            scount[j] = j + 1;
            rcount[j] = rank + 1;
            sdisp[j] = stot * (int)sizeof(int);
            rdisp[j] = rtot * (int)sizeof(int);
            stype[j] = MPI_INT;
            rtype[j] = MPI_INT;
            stot += scount[j];
            rtot += rcount[j];
        }
        int *sbuf = malloc(stot * sizeof(int));
        int *rbuf = malloc(rtot * sizeof(int));
        for (int j = 0, k = 0; j < size; j++)
            for (int i = 0; i < scount[j]; i++, k++)
                sbuf[k] = 10000 * rank + 100 * j + i;
        memset(rbuf, 0xff, rtot * sizeof(int));
        CHECK(MPI_Alltoallw(sbuf, scount, sdisp, stype, rbuf, rcount,
                            rdisp, rtype, MPI_COMM_WORLD)
              == MPI_SUCCESS, 31);
        for (int j = 0; j < size; j++)
            for (int i = 0; i < rank + 1; i++)
                CHECK(rbuf[rdisp[j] / 4 + i]
                          == 10000 * j + 100 * rank + i, 32);
        free(sbuf);
        free(rbuf);
    }

    MPI_Barrier(MPI_COMM_WORLD);
    printf("OK c24_io_rma rank=%d/%d\n", rank, size);
    MPI_Finalize();
    return 0;
}
